"""The dictionary's own measurement: the merged-elements counter, the flush
cost it shares a helper with, the facade's named programs and host spans,
and the core's name scopes.

The counter is checked against the benchmark's independent model of the
LSM's counter r (`bench/work.py`), over a toy cycle of stages, a flush, a
maintain and a cleanup, on one LSM and on 1, 2 and 4 shards.
"""

import glob
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import Dictionary, QueryPlan
from repro.api import dictionary as facade
from repro.core import semantics as sem

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from bench import work  # noqa: E402

B, L = 16, 5


def _create(backend, shards=1):
    opts = {"num_shards": shards} if backend == "lsm_sharded" else {}
    return Dictionary.create(backend, batch_size=B, num_levels=L, validate=False, **opts)


def test_flush_cost_is_what_a_push_merges():
    """At every r, `flush_cost_estimate()` is b * 2^t (t the trailing ones
    of r), and the flush then adds exactly that to the counter."""
    d = _create("lsm")
    for r in range((1 << L) - 1):
        d = d.insert(np.arange(3) + 3 * r, np.ones(3, np.int32))
        t = work.trailing_ones(r)
        assert int(d.flush_cost_estimate()) == B << t, r
        before = d.counters()["merged_elements"]
        d = d.flush()
        assert d.counters()["merged_elements"] - before == B << t, r
    assert int(d.state.r) == (1 << L) - 1
    assert int(d.flush_cost_estimate()) == 0  # nothing staged


def _owned(d, keys):
    """Lanes each shard keeps of a batch of keys."""
    if d.num_shards == 1:
        return [len(keys)]
    size = d._backend.cfg.range_size
    return np.bincount(np.minimum(keys // size, d.num_shards - 1), minlength=d.num_shards)


@pytest.mark.parametrize("backend,shards", [("lsm", 1), ("lsm_sharded", 1),
                                            ("lsm_sharded", 2), ("lsm_sharded", 4)])
def test_counter_follows_the_work_model(backend, shards):
    """Staged updates of ragged widths, a flush, a maintain, a cleanup and
    more updates: the state's counter, summed over shards, is the model's
    merged count at each step."""
    d = _create(backend, shards)
    model = [work.LsmCounter(B, L, 0) for _ in range(d.num_shards)]
    rng = np.random.default_rng(shards)
    live = set()

    def updates(n):
        nonlocal d
        for _ in range(n):
            width = int(rng.integers(1, B + 1))
            keys = rng.integers(0, sem.MAX_USER_KEY, width)
            dels = rng.random(width) < 0.3
            d = d.update(keys, keys.astype(np.int32), is_delete=dels)
            for k, dl in zip(keys.tolist(), dels.tolist()):
                (live.discard if dl else live.add)(k)
            for c, lanes in zip(model, _owned(d, keys)):
                c.stage(int(lanes))

    def check():
        assert d.counters() == {"merged_elements": sum(c.merged for c in model)}

    check()
    updates(12)
    check()
    d = d.flush()
    for c in model:
        if c.buffered:
            c._push()
            c.buffered = 0
    check()
    updates(5)
    d = d.maintain(3 * B)          # levels 0 and 1 of every shard: 3 batches
    for c in model:
        c.merged += 3 * B
    check()
    d = d.cleanup()
    keys = np.asarray(sorted(live))
    for c, n in zip(model, _owned(d, keys)):
        c.cleanup(int(n))
    check()
    updates(6)
    check()
    assert sum(c.merged for c in model) > 0


@pytest.mark.parametrize("backend,shards", [("lsm", 1), ("lsm_sharded", 4)])
def test_counters_run_no_program(backend, shards):
    d = _create(backend, shards).insert(np.arange(40), np.ones(40, np.int32)).flush()
    jax.block_until_ready(d.state)
    compiled = []

    def on(name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            compiled.append(name)

    jax.monitoring.register_event_duration_secs_listener(on)
    try:
        assert d.counters()["merged_elements"] > 0
    finally:
        jax.monitoring.unregister_event_duration_listener(on)
    assert compiled == []


@pytest.mark.parametrize("backend", ["sorted_array", "cuckoo"])
def test_counters_empty_without_a_counter(backend):
    assert Dictionary.create(backend, capacity=64).counters() == {}


def _programs(d):
    """op -> (executable, arguments): every program the facade dispatches."""
    st, lanes = d.state, jax.ShapeDtypeStruct((8,), jnp.int32)
    flags = jax.ShapeDtypeStruct((8,), bool)
    plan = QueryPlan(max_candidates=16, max_results=4)
    cached = lambda op, fn: facade._cached_exec(d._backend, op, fn)  # noqa: E731
    return {
        "update": (d._update_exec(), (st, lanes, lanes, flags, flags)),
        "flush": (d._flush_exec(), (st,)),
        "maintain": (d._maintain_exec(B), (st,)),
        "cleanup": (d._cleanup_exec(), (st,)),
        "bulk_build": (d._bulk_build_exec(), (lanes, lanes)),
        "lookup": (d._lookup_exec(), (st, lanes)),
        "count": (d._window_exec("count", plan), (st, lanes, lanes)),
        "range": (d._window_exec("range", plan), (st, lanes, lanes)),
        "size": (cached("size", facade._exec_size), (st,)),
        "pending": (cached("pending", facade._exec_pending), (st,)),
        "occupancy": (cached("occupancy", facade._exec_occupancy), (st,)),
        "flush_cost": (cached("flush_cost", facade._exec_flush_cost), (st,)),
    }


OPS = ["update", "flush", "maintain", "cleanup", "bulk_build", "lookup", "count", "range",
       "size", "pending", "occupancy", "flush_cost"]


@pytest.mark.parametrize("op", OPS)
def test_facade_program_is_named_after_its_op(op):
    f, args = _programs(_create("lsm"))[op]
    assert f.__name__ == f"_exec_{op}"  # the host dispatch's name
    assert f.lower(*args).as_text().startswith(f"module @jit__exec_{op} ")


def _op_names(f, args):
    text = f.lower(*args).compile().as_text()
    return {part for line in text.splitlines() if 'op_name="' in line
            for part in line.split('op_name="')[1].split('"')[0].split("/")}


@pytest.mark.parametrize("backend,shards", [("lsm", 1), ("lsm_sharded", 2)])
def test_update_path_runs_under_its_scopes(backend, shards):
    programs = _programs(_create(backend, shards))
    assert {"lsm.stage", "lsm.push"} <= _op_names(*programs["update"])
    flush = _op_names(*programs["flush"])
    assert "lsm.push" in flush and "lsm.stage" not in flush
    assert not {"lsm.stage", "lsm.push"} & _op_names(*programs["lookup"])


def test_public_methods_open_their_spans(tmp_path):
    """Each public method that dispatches a program opens `dictionary.<op>`
    on the host; update's input handling nests in it as `.prepare`."""
    from jax.profiler import ProfileData

    d = _create("lsm")
    calls = [("bulk_build", lambda d: d.bulk_build(np.arange(20), np.ones(20, np.int32))),
             ("update", lambda d: d.update(np.arange(4), np.ones(4, np.int32))),
             ("flush", lambda d: d.flush()), ("maintain", lambda d: d.maintain(B)),
             ("cleanup", lambda d: d.cleanup()), ("lookup", lambda d: d.lookup(np.arange(4))),
             ("count", lambda d: d.count(np.arange(4), np.arange(4) + 2)),
             ("range", lambda d: d.range(np.arange(4), np.arange(4) + 2, QueryPlan(max_results=4)))]
    for _, call in calls:  # compile outside the trace
        out = call(d)
        if isinstance(out, Dictionary):
            d = out
    jax.block_until_ready(d.state)
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _, call in calls:
            out = call(d)
            if isinstance(out, Dictionary):
                d = out
        jax.block_until_ready(d.state)
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"), recursive=True)[0]
    events = [e for p in ProfileData.from_file(path).planes for ln in p.lines for e in ln.events]
    spans = {}
    for e in events:
        spans.setdefault(e.name, []).append((e.start_ns, e.start_ns + e.duration_ns))
    assert {f"dictionary.{op}" for op, _ in calls} | {"dictionary.update.prepare"} <= set(spans)
    (p0, p1), = spans["dictionary.update.prepare"]
    assert any(a <= p0 and p1 <= b for a, b in spans["dictionary.update"])
    assert "PjitFunction(_exec_update)" in spans  # dispatch names keep the op body's name
