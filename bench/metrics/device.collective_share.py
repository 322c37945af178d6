"""Share of the traced window a device spent in collectives (all-gather,
all-reduce, collective-permute, all-to-all, reduce-scatter), averaged over
the cell's chips. Nothing to read where no collective ran."""


def read(ctx):
    t = ctx["trace"]
    if t is None:
        return None
    share = sum(d.collective_s for d in t.devices) / len(t.devices) / t.window_s
    return 100.0 * share if share > 0 else None
