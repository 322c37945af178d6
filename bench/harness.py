"""Run one cell: find its files by name, set up, measure, check, report.

Everything particular to a cell lives in files that `BENCHMARK.json` names:

* the configuration, `configs[].file` (sizes, backend, guarantees);
* the traffic mix, `bench/traffic/<traffic>.json`, whose `loop` picks one
  of the general loops in `bench/loops.py`;
* each per-layer metric, `bench/metrics/<metric>.py`, a reader with
  `read(ctx) -> float | None` (None when the run has nothing to read).

So a later cell, mix or metric is added as files and entries only.
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys
import tempfile
import time
from typing import Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GIB = float(1 << 30)


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def load_spec(root: str, workload: str) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json: {sorted(cells)}")
    cell = cells[workload]
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(root, config["file"])) as f:
        cfg = json.load(f)
    with open(os.path.join(root, "bench", "traffic", cell["traffic"] + ".json")) as f:
        traffic = json.load(f)

    def mine(metric):
        return workload in metric.get("workloads", [workload])

    return {"cell": cell, "config": cfg, "traffic": traffic,
            "end_to_end": [m for m in bench["end_to_end"] if mine(m)],
            "per_layer": [m for m in bench["per_layer"] if mine(m)]}


def read_metric(root: str, name: str, ctx: dict):
    path = os.path.join(root, "bench", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("bench_metric_" + name.replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read(ctx)


def peaks_for(root: str, kind: str) -> dict:
    with open(os.path.join(root, "bench", "peaks.json")) as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in bench/peaks.json")
    return table[kind]


class CompileCount:
    """Backend compiles seen since it was made (persistent-cache hits too)."""

    def __init__(self):
        from jax import monitoring

        self.n = 0
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.n += 1


def _peak_bytes(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices]
    return int(max(peaks))


def run(workload: str, seed: int, seconds: float, trace: bool, *, root: str = ROOT,
        t_start: Optional[float] = None, require_chip: bool = True,
        control: bool = False) -> dict:
    """One run of a cell. Returns {"result": the result line, "checks":
    the numbers compared, "control": the control's, when asked}."""
    import jax

    from bench.loops import LOOPS

    t_start = time.perf_counter() if t_start is None else t_start
    spec = load_spec(root, workload)
    chips = spec["cell"]["chips"]
    devices = jax.devices()
    t_devices = time.perf_counter()
    if require_chip and devices[0].platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found {devices[0].platform}")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips; JAX found {len(devices)}")
    devices = devices[:chips]
    peaks = {}
    if require_chip:
        peaks = peaks_for(root, devices[0].device_kind)
        # The persistent compile cache sits at a fixed path inside the
        # checkout, whatever the environment names, so that two checkouts
        # share nothing; it keeps every program of the cell, however fast it
        # compiles, so that a checkout's later runs compile nothing.
        jax.config.update("jax_compilation_cache_dir", os.path.join(root, ".jax_cache"))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    compiles = CompileCount()

    from repro.kernels import ops

    loop = LOOPS[spec["traffic"]["loop"]](spec["config"], spec["traffic"], seed, trace)
    with ops.record_paths() as paths:
        loop.setup()
    setup_s = time.perf_counter() - t_start
    print(f"kernel paths: {sorted({f'{op}:{path}' for op, path in paths})}", flush=True)
    phases = dict(start_and_devices=t_devices - t_start, **loop.setup_s)
    print("setup phases (s): " + json.dumps(phases), flush=True)
    c0 = compiles.n
    summary = None
    if trace:
        from bench import trace as tr

        with tempfile.TemporaryDirectory() as tmp:
            with tr.capture(tmp):
                loop.window(seconds)
            summary = tr.reduce(tr.from_profile(tmp))
    else:
        loop.window(seconds)
    window_compiles = compiles.n - c0
    print("slowest calls (index, ms, dispatch ms, s into window): "
          + json.dumps(loop.slowest()), flush=True)
    peak = _peak_bytes(devices)
    checks = loop.checks()
    out = {"checks": checks}
    if control:
        out["control"] = loop.checks(control=True)

    if trace:
        ctx = {"trace": summary, "window_s": loop.window_s, "spans": loop.spans,
               "counters": loop.counters, "work": loop.work(), "peaks": peaks}
        metrics = {}
        for m in spec["per_layer"]:
            value = read_metric(root, m["name"], ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = dict(loop.end_to_end(), setup_s=setup_s, hbm_peak_gib=peak / GIB)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in spec["end_to_end"]}
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    result = {"correct": all(c.ok for c in checks), "attempted": loop.ops, "failed": 0,
              "metrics": metrics, "device": device}
    if trace and summary is not None:
        device.update(busy_s=summary.busy_s, window_s=summary.window_s)
        result["breakdown"] = summary.breakdown()
    result["window_compiles"] = window_compiles
    result["compared"] = {c.name: {"value": c.value, "limit": c.limit} for c in checks}
    out["result"] = result
    return out


def main(args, t_start: float) -> int:
    try:
        out = run(args.workload, args.seed, args.seconds, bool(args.trace), t_start=t_start)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    result = out["result"]
    for c in out["checks"]:
        print(f"compared {c.name} = {c.value} (limit {c.limit})", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0
