"""Share of the roofline in the update programs (`_exec_update`,
`_exec_flush`, `_exec_cleanup`): the least time their merges need at the
chip's HBM bandwidth (bench/work.py: 16 bytes per element merged, the
elements from the LSM counter r), over their device time in the trace."""

PROGRAMS = ("_exec_update", "_exec_flush", "_exec_cleanup")


def read(ctx):
    t, least = ctx["trace"], ctx["work"].get("update_bytes")
    if t is None or not least:
        return None
    device_s = t.module_s(*PROGRAMS) / len(t.devices)
    if device_s <= 0:
        return None
    return 100.0 * least / ctx["peaks"]["hbm_bytes_per_s"] / device_s
