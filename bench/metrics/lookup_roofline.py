"""Share of the roofline in the lookup program (`_exec_lookup`): the least
time its bytes need at the chip's HBM bandwidth (bench/work.py: 9 bytes per
query plus 4 per occupied run), over its device time in the trace."""


def read(ctx):
    t, least = ctx["trace"], ctx["work"].get("lookup_bytes")
    if t is None or not least:
        return None
    device_s = t.module_s("_exec_lookup") / len(t.devices)
    if device_s <= 0:
        return None
    return 100.0 * least / ctx["peaks"]["hbm_bytes_per_s"] / device_s
