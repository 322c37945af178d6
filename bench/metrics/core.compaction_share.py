"""Share of the window's wall time spent in cleanups: the harness's span
around each `Dictionary.cleanup()` call, up to its acknowledgement."""


def read(ctx):
    s = ctx["spans"].get("cleanup")
    if not s:
        return None
    return 100.0 * s / ctx["window_s"]
