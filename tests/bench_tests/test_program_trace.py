"""CPU tests of the program's own measurement as the benchmark would read
it: `bench/program_trace.py` on traces built by hand and on a CPU trace of
the facade, and the program's merged-elements counter against the work
model over a window of the tiny update cell.
"""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [REPO, os.path.join(REPO, "src")]

from bench import harness  # noqa: E402
from bench import program_trace as pt  # noqa: E402
from bench import trace  # noqa: E402
from test_bench_harness import tiny_root  # noqa: E402

STAGE = "jit(_exec_update)/lsm.stage/cond/branch_1_fun"


def _ev(name, start, dur):
    return trace.Event(name, start, dur)


def _op(name, start, dur, scope=""):
    return pt.Op(name, start, dur, scope)


def built_trace():
    """A 1000 ns window on two devices.

    Host: `dictionary.update` 1000-1200 holding `.prepare` 1000-1050, and
    `dictionary.flush` 1600-1700. Device 0: an update program 1100-1500
    (ops: a stage sort 1100-1200, a push fusion 1200-1300 nested in a stage
    `while` 1200-1350, an unscoped fusion 1400-1500: idle 1350-1400 inside
    it), and a flush program 1700-1800 (a push sort 1700-1750). Device 1:
    the update program 1100-1300 (a stage sort, the whole of it)."""
    host = trace.Plane("/host:CPU", {"python": [
        _ev("bench.window", 1000, 1000), _ev("dictionary.update", 1000, 200),
        _ev("dictionary.update.prepare", 1000, 50), _ev("dictionary.flush", 1600, 100),
        _ev("bench.ack", 1200, 300)]})
    dev0 = trace.Plane("/device:TPU:0", {
        trace.OPS_LINE: [_op("sort.1", 1100, 100, STAGE + "/sort"),
                         _op("while.2", 1200, 150, STAGE + "/while"),
                         _op("fusion.3", 1200, 100, STAGE + "/lsm.push/switch/fusion"),
                         _op("fusion.4", 1400, 100, "jit(_exec_update)/add"),
                         _op("sort.5", 1700, 50, "jit(_exec_flush)/cond/lsm.push/sort")],
        trace.MODULES_LINE: [_ev("jit__exec_update(11)", 1100, 400),
                             _ev("jit__exec_flush(22)", 1700, 100)]})
    dev1 = trace.Plane("/device:TPU:1", {
        trace.OPS_LINE: [_op("sort.1", 1100, 200, STAGE + "/sort")],
        trace.MODULES_LINE: [_ev("jit__exec_update(11)", 1100, 200)]})
    return [host, dev0, dev1]


def test_scope_is_the_innermost_of_stage_and_push():
    assert pt.scope_of(STAGE + "/lsm.push/sort") == "lsm.push"
    assert pt.scope_of(STAGE + "/sort") == "lsm.stage"
    assert pt.scope_of("lsm.push/x/lsm.stage/y") == "lsm.stage"
    assert pt.scope_of("jit(_exec_lookup)/while/body") == ""
    assert pt.scope_of("") == ""


def test_interval_algebra():
    a = [(0, 10), (20, 30), (40, 50)]
    b = [(5, 25), (45, 60)]
    assert pt._intersect(a, b) == [(5, 10), (20, 25), (45, 50)]
    assert pt._subtract(a, b) == [(0, 5), (25, 30), (40, 45)]
    assert pt._subtract(a, []) == a and pt._intersect(a, []) == []


def test_reduction_on_a_built_trace():
    p = pt.reduce(built_trace())
    assert p.window_s == pytest.approx(1e-6)
    d0, d1 = p.devices
    # device 0: the while op's own time is 50 ns once the push nested in it is taken out
    assert d0.scope_s == pytest.approx({("_exec_update", "lsm.stage"): 150e-9,
                                        ("_exec_update", "lsm.push"): 100e-9,
                                        ("_exec_update", ""): 100e-9,
                                        ("_exec_flush", "lsm.push"): 50e-9})
    assert d0.module_s == pytest.approx({"_exec_update": 400e-9, "_exec_flush": 100e-9})
    # busy 1100-1350, 1400-1500, 1700-1750; idle in programs 1350-1400 and 1750-1800
    assert d0.idle_in_programs_s == pytest.approx(100e-9)
    # facade spans 1000-1200 and 1600-1700, less the programs: 1000-1100 and 1600-1700
    assert d0.idle_in_facade_s == pytest.approx(200e-9)
    assert d1.idle_in_programs_s == pytest.approx(0.0)
    assert d1.idle_in_facade_s == pytest.approx(200e-9)
    assert p.spans_s == pytest.approx({"dictionary.update": 200e-9,
                                       "dictionary.update.prepare": 50e-9,
                                       "dictionary.flush": 100e-9})
    # each device: stage + push + unscoped = its busy time in the programs
    for d, busy in ((d0, 400e-9), (d1, 200e-9)):
        assert sum(d.scope_s.values()) == pytest.approx(busy)
    # the split never exceeds the idle the existing reduction reads
    t = trace.reduce(built_trace())
    for d, dt in zip(p.devices, t.devices):
        idle = t.window_s - dt.busy_s
        assert d.idle_in_programs_s + d.idle_in_facade_s <= idle + 1e-15
    # averaged over the devices, as the per-layer metrics read them
    assert p.scope_s("lsm.stage", "_exec_update", "_exec_flush") == pytest.approx(175e-9)
    assert p.scope_s("lsm.push", "_exec_update", "_exec_flush") == pytest.approx(75e-9)
    assert p.scope_s("", "_exec_update") == pytest.approx(50e-9)
    assert p.module_s("_exec_update") == pytest.approx(300e-9)
    assert p.idle_in_programs_s / p.window_s == pytest.approx(0.05)
    assert p.idle_in_facade_s / p.window_s == pytest.approx(0.20)


def test_device_events_move_after_their_dispatch():
    """The trace's clocks disagree: every device event shows 200 ns early,
    so programs start before the host dispatched them (at 1100 and 1700).
    The device moves 200 ns later and reads as the built trace does; with a
    dispatch missing the pairing is unknown and nothing moves."""
    host, dev0, dev1 = built_trace()
    early = lambda p: trace.Plane(p.name, {  # noqa: E731
        line: [e._replace(start_ns=e.start_ns - 200) for e in events]
        for line, events in p.lines.items()})
    # each call records two nested dispatch events, as a TPU host trace does
    dispatch = [_ev("PjitFunction(_exec_update)", 1100, 5), _ev("PjitFunction(_exec_update)", 1101, 3),
                _ev("PjitFunction(_exec_flush)", 1700, 5), _ev("PjitFunction(_exec_flush)", 1701, 3)]
    host = trace.Plane(host.name, {"python": host.lines["python"] + dispatch})
    p, want = pt.reduce([host, early(dev0), early(dev1)]), pt.reduce(built_trace())
    assert [d.shift_s for d in p.devices] == pytest.approx([200e-9, 200e-9])
    for d, w in zip(p.devices, want.devices):
        assert d.idle_in_facade_s == pytest.approx(w.idle_in_facade_s)
        assert d.idle_in_programs_s == pytest.approx(w.idle_in_programs_s)
        assert d.scope_s == pytest.approx(w.scope_s)
    host = trace.Plane(host.name, {"python": host.lines["python"][:-2]})
    p = pt.reduce([host, early(dev0), early(dev1)])
    assert [d.shift_s for d in p.devices] == pytest.approx([200e-9, 200e-9])  # update pairs
    host = trace.Plane(host.name, {"python": host.lines["python"][:-2]})
    assert [d.shift_s for d in pt.reduce([host, early(dev0)]).devices] == [0.0]


def test_a_program_without_the_names_reads_nothing():
    """A program without the spans, the scopes or the named modules, as the
    parent of this measurement ran: nothing is split, though the device ran;
    and a trace with no window reduces to None."""
    host, dev0, dev1 = built_trace()
    bare = lambda p: trace.Plane(p.name, {  # noqa: E731
        trace.OPS_LINE: [_op(e.name, e.start_ns, e.dur_ns) for e in p.lines[trace.OPS_LINE]],
        trace.MODULES_LINE: [_ev("jit__unknown(7)", e.start_ns, e.dur_ns)
                             for e in p.lines[trace.MODULES_LINE]]})
    host = trace.Plane(host.name, {"python": [e for e in host.lines["python"]
                                              if e.name.startswith("bench.")]})
    p = pt.reduce([host, bare(dev0), bare(dev1)])
    assert p.spans_s == {}
    assert p.module_s("_exec_update", "_exec_flush") == 0.0
    assert p.scope_s("lsm.stage", "_exec_update") == p.scope_s("lsm.push", "_exec_update") == 0.0
    assert p.idle_in_programs_s == p.idle_in_facade_s == 0.0
    for d, busy in zip(p.devices, (400e-9, 200e-9)):
        assert d.scope_s == pytest.approx({("?", ""): busy})
    assert pt.reduce([dev0, dev1]) is None


@pytest.mark.parametrize("workload,merges", [("paper-update", True), ("paper-lookup", False)])
def test_program_counter_is_the_work_model(tmp_path, workload, merges):
    """Across a traced window of the tiny cell, the difference of the
    program's `counters()` is what `bench/work.py`'s model of r counts (16
    bytes an element merged): 0 where the cell only looks keys up."""
    from bench.loops import LOOPS

    spec = harness.load_spec(tiny_root(tmp_path), workload)
    loop = LOOPS[spec["traffic"]["loop"]](spec["config"], spec["traffic"], 2**31 + 77, True)
    loop.setup()
    before = loop.d.counters()["merged_elements"]
    loop.window(0.3)
    merged = loop.d.counters()["merged_elements"] - before
    assert merged == loop.work()["update_bytes"] / 16
    assert (merged > 0) == merges
    if merges:  # whole cycles: 1,023 pushes and a cleanup's 2^L batches per 1,024
        b, cycle = spec["config"]["batch_size"], spec["config"]["cleanup_every_updates"]
        assert loop.counters["update_batches"] % cycle == 0
        assert merged / (loop.counters["update_batches"] * b) > 1


def _pb(field, payload: bytes) -> bytes:
    """One length-delimited protobuf field (field numbers below 16)."""
    n, size = len(payload), b""
    while True:
        size += bytes([(n & 0x7F) | (0x80 if n > 0x7F else 0)])
        n >>= 7
        if not n:
            break
    return bytes([field << 3 | 2]) + size + payload


def _hlo(op_names: dict) -> bytes:
    """An xla.HloProto: one computation whose instructions carry op_names."""
    insts = b"".join(_pb(2, _pb(1, name.encode()) + _pb(7, _pb(2, path.encode())))
                     for name, path in op_names.items())
    return _pb(1, _pb(3, _pb(1, b"main") + insts))


def test_scopes_come_from_the_hlo_where_ops_carry_no_stat(tmp_path):
    """A TPU trace gives its ops no `tf_op` stat: each op then takes the
    op_name of its instruction in the HLO the XSpace keeps of its module
    (plane /host:metadata, stat "Hlo Proto")."""
    from jax.profiler import ProfileData

    hlo = _hlo({"sort.1": STAGE + "/sort", "fusion.3": STAGE + "/lsm.push/fusion",
                "fusion.4": "jit(_exec_update)/add"})
    escaped = "".join(f"\\{b:03o}" for b in hlo)
    ev = lambda md, start, dur: (f"events {{ metadata_id: {md} offset_ps: {start * 1000} "  # noqa: E731
                                 f"duration_ps: {dur * 1000} }}")
    text = f"""
planes {{ id: 1 name: "/host:CPU"
  lines {{ id: 1 name: "python" timestamp_ns: 0 {ev(1, 1000, 1000)} {ev(2, 1000, 150)} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "bench.window" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "dictionary.update" }} }} }}
planes {{ id: 2 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Modules" timestamp_ns: 0 {ev(1, 1100, 400)} }}
  lines {{ id: 2 name: "XLA Ops" timestamp_ns: 0 {ev(2, 1100, 100)} {ev(3, 1200, 100)}
    {ev(4, 1400, 100)} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "jit__exec_update(11)" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "%sort.1 = s32[8] sort(s32[8] %p)" }} }}
  event_metadata {{ key: 3 value {{ id: 3 name: "%fusion.3 = s32[8] fusion(s32[8] %a)" }} }}
  event_metadata {{ key: 4 value {{ id: 4 name: "%fusion.4 = s32[8] fusion(s32[8] %b)" }} }} }}
planes {{ id: 3 name: "/host:metadata"
  event_metadata {{ key: 1 value {{ id: 1 name: "jit__exec_update(11)"
    stats {{ metadata_id: 7 bytes_value: "{escaped}" }} }} }}
  stat_metadata {{ key: 7 value {{ id: 7 name: "Hlo Proto" }} }} }}
"""
    profile = tmp_path / "plugins" / "profile" / "run"
    profile.mkdir(parents=True)
    (profile / "host.xplane.pb").write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    assert pt.hlo_op_names(str(profile / "host.xplane.pb"))["jit__exec_update(11)"]["sort.1"] \
        == STAGE + "/sort"
    planes = pt.from_profile(str(tmp_path))
    dev = next(p for p in planes if p.name == "/device:TPU:0")
    assert [op.scope for op in dev.lines[trace.OPS_LINE]] == [
        STAGE + "/sort", STAGE + "/lsm.push/fusion", "jit(_exec_update)/add"]
    p = pt.reduce(planes)
    assert p.devices[0].scope_s == pytest.approx({("_exec_update", "lsm.stage"): 100e-9,
                                                  ("_exec_update", "lsm.push"): 100e-9,
                                                  ("_exec_update", ""): 100e-9})
    assert p.idle_in_programs_s == pytest.approx(100e-9)  # 1300-1400
    assert p.idle_in_facade_s == pytest.approx(100e-9)    # 1000-1100
    assert p.spans_s == pytest.approx({"dictionary.update": 150e-9})


def test_hlo_of_a_traced_facade_program_names_its_scopes(tmp_path):
    """On a real (CPU) trace the XSpace keeps the facade's update program,
    whose instructions carry the `lsm.stage` and `lsm.push` scopes."""
    import glob

    import jax
    import numpy as np

    from repro.api import Dictionary

    d = Dictionary.create("lsm", batch_size=64, num_levels=3, validate=False)
    d = d.update(np.arange(64), np.ones(64, np.int32))
    jax.profiler.start_trace(str(tmp_path))
    try:
        d = d.update(np.arange(64) + 64, np.ones(64, np.int32))
        jax.block_until_ready(d.state)
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0]
    names = pt.hlo_op_names(path)
    update = [names[m] for m in names if m.startswith("jit__exec_update(")]
    assert any({pt.scope_of(v) for v in ops.values()} == {"", "lsm.stage", "lsm.push"}
               for ops in update)
