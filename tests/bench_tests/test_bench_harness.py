"""CPU tests of the chip benchmark (bench/), at a tiny scale.

Each cell of BENCHMARK.json runs end to end here on small copies of its
configuration (the harness's look for a chip skipped): its traffic, the
program and the reference must agree. The control, and each fault that a
cell can have, planted under the timed path, must make `correct` false. The
trace reduction is checked on a trace built by hand; the command must refuse
to run without a TPU; and a cell added as new files only must be found by
name.
"""

import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [REPO, os.path.join(REPO, "src")]

from bench import data, harness, reference, trace, work  # noqa: E402

# Each configuration cut to a size the CPU runs in seconds; the keys that
# change are scale only.
TINY = {
    "paper-n2e27": dict(batch_size=256, num_levels=7, bulk_batches=31, cleanup_every_updates=8),
    "paper-sharded-n2e28": dict(batch_size=256, num_levels=7, bulk_batches=63,
                                cleanup_every_updates=8),
}
TINY_LOOKUP_WIDTH = 256


def tiny_root(tmp_path) -> str:
    """A checkout holding BENCHMARK.json and bench/, cut to TINY."""
    root = str(tmp_path / "checkout")
    shutil.copytree(os.path.join(REPO, "bench"), os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    for name, cut in TINY.items():
        path = os.path.join(root, "bench", "configs", name + ".json")
        with open(path) as f:
            cfg = json.load(f)
        cfg.update(cut)
        with open(path, "w") as f:
            json.dump(cfg, f)
    # The four-chip cell waits for its chip runs (PERF.md); its files drive
    # the sharded path here all the same.
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "paper-sharded-n2e28",
                             "file": "bench/configs/paper-sharded-n2e28.json"})
    bench["workloads"].append({"name": "paper-mixed-4chip", "config": "paper-sharded-n2e28",
                               "traffic": "paper-mixed", "chips": 4})
    with open(path, "w") as f:
        json.dump(bench, f)
    for mix in ("paper-lookup", "paper-mixed"):
        path = os.path.join(root, "bench", "traffic", mix + ".json")
        with open(path) as f:
            tr = json.load(f)
        tr["lookup"].update(width=TINY_LOOKUP_WIDTH, pool=4)
        with open(path, "w") as f:
            json.dump(tr, f)
    return root


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root(tmp_path_factory.mktemp("bench"))


def run(root, workload, seed=123456789012, seconds=0.3, **kw):
    return harness.run(workload, seed, seconds, False, root=root, require_chip=False, **kw)


CELLS = [w["name"] for w in json.load(open(os.path.join(REPO, "BENCHMARK.json")))["workloads"]]
CELLS.append("paper-mixed-4chip")


@pytest.mark.parametrize("workload", CELLS)
def test_cell_matches_reference_and_control_fails(root, workload):
    out = run(root, workload, control=True)
    res = out["result"]
    assert res["correct"], res["compared"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["window_compiles"] == 0
    names = {m["name"] for m in harness.load_spec(root, workload)["end_to_end"]}
    assert set(res["metrics"]) == names
    # The CPU reports no device memory; every other metric is above 0.
    assert all(v["value"] > 0 for k, v in res["metrics"].items() if k != "hbm_peak_gib")
    assert list(res)[-1] == "compared"
    assert not all(c.ok for c in out["control"]), out["control"]


@pytest.mark.parametrize("workload,expect", [
    ("paper-update", {"core.compaction_share"}),
    ("paper-mixed-4chip", set()),
])
def test_traced_run_reports_what_a_cpu_trace_holds(root, workload, expect):
    """The traced path end to end; the CPU trace has no TPU planes, so the
    device metrics are left out rather than read as 0."""
    res = harness.run(workload, 99, 0.3, True, root=root, require_chip=False)["result"]
    assert res["correct"]
    assert set(res["metrics"]) == expect
    assert all(v["value"] > 0 for v in res["metrics"].values())


def test_update_window_holds_whole_cycles(root):
    """However the seconds fall, an update window ends just after a cleanup,
    so every window holds the same share of cleanup work: a window whose
    seconds pass during its first call still runs one whole cycle."""
    cfg = TINY["paper-n2e27"]
    ops = run(root, "paper-update", seconds=1e-6)["result"]["attempted"]
    assert ops == cfg["cleanup_every_updates"] * cfg["batch_size"]


def _faulty_update(kind):
    """Dictionary.update with a fault planted where its answer is made."""
    from repro.api import Dictionary

    real = Dictionary.update

    def update(self, keys, values=None, is_delete=None, valid=None):
        if kind == "unchanged":
            return self
        n = np.shape(keys)[0]
        half = np.arange(n) < n // 2
        valid = half if valid is None else np.asarray(valid) & half
        return real(self, keys, values, is_delete, valid)

    return update


def _altered_lookup():
    from repro.api import Dictionary

    real = Dictionary.lookup

    def lookup(self, keys):
        found, vals = real(self, keys)
        return found, vals.at[0].add(1)

    return lookup


FAULTS = [(w, f) for w in CELLS for f in ("unchanged", "half_batch", "answer")
          if not (w == "paper-lookup" and f != "answer")]


@pytest.mark.parametrize("workload,fault", FAULTS)
def test_fault_makes_run_incorrect(root, monkeypatch, workload, fault):
    from repro.api import Dictionary

    if fault == "answer":
        monkeypatch.setattr(Dictionary, "lookup", _altered_lookup())
    else:
        monkeypatch.setattr(Dictionary, "update", _faulty_update(
            "unchanged" if fault == "unchanged" else "half"))
    assert not run(root, workload)["result"]["correct"]


def test_exchange_left_out_makes_sharded_run_incorrect(root, monkeypatch):
    """The psum that combines the owners' lookup answers, left out."""
    import jax

    from repro.api import dictionary

    monkeypatch.setattr(jax.lax, "psum", lambda x, axis_name, **kw: x)
    monkeypatch.setattr(dictionary, "_EXEC_CACHE", {})
    jax.clear_caches()
    try:
        assert not run(root, "paper-mixed-4chip")["result"]["correct"]
    finally:
        monkeypatch.undo()
        dictionary._EXEC_CACHE.clear()
        jax.clear_caches()


def test_reference_follows_the_schedule():
    """Replaying the schedule's batches in order into a dict gives what the
    reference reads after any number of them."""
    keys = data.stream_keys(42)
    s = data.Strata(4000, keys)
    churn = data.Churn(s, 64, 0.5, 5)
    parity = churn.batches(np, np.arange(churn.cycle))
    state = {int(x): int(v) for x, v in zip(s.resident(np, np.arange(s.n)),
                                            s.bulk_value(np, np.arange(s.n)))}
    probe = np.unique(np.concatenate([parity[0][0].ravel(), s.absent(np, np.arange(50)),
                                      s.resident(np, np.arange(50))]))
    for m in range(1, 3 * churn.cycle + 2):
        i, c = (m - 1) % churn.cycle, (m - 1) // churn.cycle
        keys, vals, dels = parity[c % 2]
        for key, v, dl in zip(keys[i].tolist(), vals[i].tolist(), dels.tolist()):
            if dl:
                state.pop(key, None)
            else:
                state[key] = v
        found, value = reference.churn_lookup(s, churn, probe, m)
        assert found.tolist() == [int(q) in state for q in probe]
        assert value[found].tolist() == [state[int(q)] for q in probe[found]]
        assert churn.live_after(m) == len(state)


def test_numpy_and_device_data_agree():
    import jax.numpy as jnp

    keys = data.stream_keys(2**31 + 12345)
    j = np.arange(1000)
    s_np = data.Strata(5000, keys)
    s_jx = data.Strata(5000, {k: jnp.uint32(v) for k, v in keys.items()})
    for fn in ("resident", "fresh", "absent", "bulk_value"):
        assert np.array_equal(getattr(s_np, fn)(np, j),
                              np.asarray(getattr(s_jx, fn)(jnp, jnp.asarray(j, jnp.int32))))
    res, fresh, absent = (getattr(s_np, f)(np, j) for f in ("resident", "fresh", "absent"))
    assert len({*res.tolist(), *fresh.tolist(), *absent.tolist()}) == 3 * len(j)


def _ev(name, start, dur):
    return trace.Event(name, start, dur)


def test_trace_reduction_on_a_built_trace():
    """Two devices, a 1000 ns window: device 0 busy 600 ns (two overlapping
    ops and one collective of 100 ns), device 1 busy 200 ns."""
    host = trace.Plane("/host:CPU", {"python": [
        _ev("bench.window", 1000, 1000), _ev("bench.ack", 1100, 300),
        _ev("bench.dispatch", 1500, 150), _ev("PjitFunction(_exec_update)", 990, 5),
        _ev("PjitFunction(_exec_lookup)", 1550, 5)]})
    dev0 = trace.Plane("/device:TPU:0", {
        trace.OPS_LINE: [_ev("%fusion.1 = s32[8] fusion(...)", 1000, 300), _ev("sort.2", 1200, 200),
                         _ev("%all-reduce.3 = s32[8] all-reduce(...)", 1600, 100),
                         _ev("fusion.4", 500, 100)],
        trace.MODULES_LINE: [_ev("jit__unknown(11)", 1000, 400), _ev("jit__unknown(22)", 1600, 100)]})
    dev1 = trace.Plane("/device:TPU:1", {trace.OPS_LINE: [_ev("fusion.1", 1900, 300)]})
    t = trace.reduce([host, dev0, dev1])
    assert t.window_s == pytest.approx(1e-6)
    assert [d.busy_s for d in t.devices] == pytest.approx([500e-9, 100e-9])
    assert t.busy_s == pytest.approx(300e-9)
    ctx = {"trace": t}
    assert harness.read_metric(REPO, "device.idle_share", ctx) == pytest.approx(70.0)
    assert harness.read_metric(REPO, "device.collective_share", ctx) == pytest.approx(5.0)
    assert t.module_s("_exec_update") == pytest.approx(400e-9)
    assert t.module_s("_exec_lookup", "_exec_update") == pytest.approx(500e-9)
    gaps = trace.attribute_gaps(t.devices[0].gaps, t.spans)
    assert gaps == pytest.approx({"bench.dispatch": 200e-9, "host: none": 300e-9})
    bd = t.breakdown()
    assert bd["device_ops"][0][0] == "_exec_update:fusion"
    assert dict(bd["device_ops"])["_exec_lookup:all-reduce"] == pytest.approx(50e-9)
    assert dict(bd["device_ops"])["?:fusion"] == pytest.approx(50e-9)
    assert trace.reduce([dev0]) is None  # no window


def test_trace_breakdown_counts_nested_ops_once():
    host = trace.Plane("/host:CPU", {"python": [_ev("bench.window", 1000, 1000)]})
    dev = trace.Plane("/device:TPU:0", {trace.OPS_LINE: [
        _ev("%while.1 = (s32[]) while(...)", 1000, 500), _ev("%fusion.2 = s32[] fusion(...)", 1100, 200),
        _ev("%fusion.3 = s32[] fusion(...)", 1300, 100)]})
    t = trace.reduce([host, dev])
    assert t.busy_s == pytest.approx(500e-9)
    assert dict(t.breakdown()["device_ops"]) == pytest.approx({"?:while": 200e-9, "?:fusion": 300e-9})


def test_work_model_counts_merges():
    c = work.LsmCounter(b=4, levels=3, r=3)
    c.stage(4)           # fits the buffer
    c.stage(4)           # pushes one batch into r = 0b11: merges 4 * 2^2
    assert (c.r, c.merged, c.runs) == (4, 16, 2)
    c.cleanup(live=9)
    assert (c.r, c.merged, c.runs) == (3, 16 + 32, 2)


def test_command_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload", "paper-lookup",
                        "--seed", "1", "--seconds", "1", "--trace", "0"],
                       cwd=REPO, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


def test_cell_added_as_files_only_is_found_by_name(tmp_path):
    root = tiny_root(tmp_path)
    with open(os.path.join(root, "bench", "traffic", "paper-lookup.json")) as f:
        mix = json.load(f)
    mix["lookup"]["resident_share"] = 1.0
    with open(os.path.join(root, "bench", "traffic", "toy-all-resident.json"), "w") as f:
        json.dump(mix, f)
    with open(os.path.join(root, "bench", "metrics", "toy.lookup_calls.py"), "w") as f:
        f.write("def read(ctx):\n    return ctx['counters'].get('lookup_calls')\n")
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["workloads"].append({"name": "toy", "config": "paper-n2e27",
                               "traffic": "toy-all-resident", "chips": 1, "why": "toy"})
    bench["per_layer"].append({"name": "toy.lookup_calls", "unit": "calls", "better": "higher",
                               "source": "program_counter", "layer": "device",
                               "moves": "ops_rate", "workloads": ["toy"]})
    with open(path, "w") as f:
        json.dump(bench, f)
    res = run(root, "toy")["result"]
    assert res["correct"]
    assert set(res["metrics"]) == {"ops_rate", "hbm_peak_gib", "setup_s"}
    spec = harness.load_spec(root, "toy")
    assert [m["name"] for m in spec["per_layer"]] == ["toy.lookup_calls"]
    assert harness.read_metric(root, "toy.lookup_calls", {"counters": {"lookup_calls": 3}}) == 3
