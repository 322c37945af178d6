"""Device traces: capture one around the measured window, reduce it to numbers.

The reduction works on plain tuples (`Plane`), so a test can build a trace
by hand. From the profiler's XSpace it keeps:

* the window: the host span named `WINDOW`, which the harness opens around
  the measured window;
* host spans whose names start with `bench.`: what the harness was doing;
* per device (`/device:TPU:<n>` planes): the "XLA Ops" events, which are the
  operations that ran, and the "XLA Modules" events, which are the programs.
  The facade's programs reach the trace as `jit__unknown(<fingerprint>)`, so
  each fingerprint is named after the host dispatch (`PjitFunction(<fn>)`)
  that most often came last before one of its executions started; the batch
  loops acknowledge every call before the next, which makes that exact.

Busy time is the union of a device's op intervals inside the window; the
idle share is 1 - busy / window. Collectives are ops whose names name one.
"""

from __future__ import annotations

import bisect
import collections
import contextlib
import dataclasses
import glob
import os
import re
from typing import Dict, List, NamedTuple, Optional, Tuple

WINDOW = "bench.window"
SPAN_PREFIX = "bench."
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_DEVICE = re.compile(r"^/device:TPU:\d+$")
_COLLECTIVE = re.compile(r"all-gather|all-reduce|collective-permute|all-to-all|reduce-scatter")
_DISPATCH = re.compile(r"^PjitFunction\((.+)\)$")
_HLO_NAME = re.compile(r"^%?([A-Za-z][\w\-]*?)(?:\.\d+)*(?: = |$)")


class Event(NamedTuple):
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


class Plane(NamedTuple):
    name: str
    lines: Dict[str, List[Event]]


@dataclasses.dataclass
class DeviceTime:
    busy_s: float
    ops_s: Dict[str, float]        # "<program>:<op kind>" -> self seconds
    modules_s: Dict[str, float]    # program (host function name) -> seconds
    collective_s: float
    gaps: List[Tuple[float, float]]  # idle intervals inside the window, ns


@dataclasses.dataclass
class Summary:
    window_s: float
    devices: List[DeviceTime]
    spans: List[Event]

    @property
    def busy_s(self) -> float:
        """Busy seconds averaged over the devices."""
        return sum(d.busy_s for d in self.devices) / len(self.devices)

    def module_s(self, *names: str) -> float:
        """Seconds in the programs of these host functions, summed over
        devices."""
        return sum(d.modules_s.get(n, 0.0) for d in self.devices for n in names)

    def breakdown(self, top: int = 10) -> dict:
        ops: Dict[str, float] = {}
        for d in self.devices:
            for name, s in d.ops_s.items():
                ops[name] = ops.get(name, 0.0) + s / len(self.devices)
        gaps: Dict[str, float] = {}
        for d in self.devices:
            for name, s in attribute_gaps(d.gaps, self.spans).items():
                gaps[name] = gaps.get(name, 0.0) + s / len(self.devices)
        order = lambda m: sorted(m.items(), key=lambda kv: -kv[1])[:top]  # noqa: E731
        return {"device_ops": [[k, v] for k, v in order(ops)],
                "idle_gaps": [[k, v] for k, v in order(gaps)]}


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def _op_kind(name: str) -> str:
    """`fusion`, `sort`, `all-reduce`, ... from an op's HLO text."""
    m = _HLO_NAME.match(name)
    return m.group(1) if m else name[:40]


def _self_times(intervals: List[Tuple[float, float]]) -> List[float]:
    """Each interval's length less the intervals nested directly in it (a
    `while` or `conditional` op holds its body's ops); intervals sorted by
    start, longest first."""
    own = [b - a for a, b in intervals]
    stack: List[int] = []
    for k, (a, b) in enumerate(intervals):
        while stack and intervals[stack[-1]][1] <= a:
            stack.pop()
        if stack and b <= intervals[stack[-1]][1]:
            own[stack[-1]] -= b - a
        stack.append(k)
    return own


def _program_names(modules: List[Event], dispatches: List[Tuple[float, str]]) -> Dict[str, str]:
    """Module name -> the host function whose dispatch most often came last
    before one of its executions started."""
    starts = [t for t, _ in dispatches]
    votes: Dict[str, collections.Counter] = collections.defaultdict(collections.Counter)
    for e in modules:
        k = bisect.bisect_right(starts, e.start_ns) - 1
        votes[e.name][dispatches[k][1] if k >= 0 else e.name] += 1
    return {name: c.most_common(1)[0][0] for name, c in votes.items()}


def reduce(planes: List[Plane]) -> Optional[Summary]:
    """Numbers of the traced window, or None when the trace has no window or
    no device ran anything in it."""
    host = [e for p in planes if not _DEVICE.match(p.name)
            for events in p.lines.values() for e in events]
    spans = [e for e in host if e.name.startswith(SPAN_PREFIX)]
    windows = [e for e in spans if e.name == WINDOW]
    if not windows:
        return None
    w0, w1 = windows[0].start_ns, windows[0].end_ns
    dispatches = sorted((e.start_ns, m.group(1)) for e in host
                        for m in [_DISPATCH.match(e.name)] if m)
    devices = []
    for p in sorted((p for p in planes if _DEVICE.match(p.name)), key=lambda p: p.name):
        inside = lambda evs: [e for e in evs if e.end_ns > w0 and e.start_ns < w1]  # noqa: E731
        ops = inside(p.lines.get(OPS_LINE, []))
        modules = sorted(inside(p.lines.get(MODULES_LINE, [])), key=lambda e: e.start_ns)
        names = _program_names(modules, dispatches)
        mod_starts = [e.start_ns for e in modules]

        def program(t: float) -> str:
            k = bisect.bisect_right(mod_starts, t) - 1
            return names[modules[k].name] if k >= 0 and modules[k].end_ns >= t else "?"

        ops.sort(key=lambda e: (e.start_ns, -e.end_ns))
        clipped = [(max(e.start_ns, w0), min(e.end_ns, w1)) for e in ops]
        busy = _union(clipped)
        ops_s: Dict[str, float] = {}
        collective = 0.0
        for e, (a, b), own in zip(ops, clipped, _self_times(clipped)):
            key = f"{program(e.start_ns)}:{_op_kind(e.name)}"
            ops_s[key] = ops_s.get(key, 0.0) + own * 1e-9
            if _COLLECTIVE.search(e.name):
                collective += (b - a) * 1e-9
        modules_s: Dict[str, float] = {}
        for e in modules:
            d = (min(e.end_ns, w1) - max(e.start_ns, w0)) * 1e-9
            modules_s[names[e.name]] = modules_s.get(names[e.name], 0.0) + d
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        gaps = [(edges[k], edges[k + 1]) for k in range(0, len(edges), 2)
                if edges[k + 1] > edges[k]]
        devices.append(DeviceTime(sum(b - a for a, b in busy) * 1e-9, ops_s,
                                  modules_s, collective, gaps))
    if not devices or not any(d.busy_s > 0 for d in devices):
        return None
    return Summary((w1 - w0) * 1e-9, devices, [e for e in spans if e.name != WINDOW])


def attribute_gaps(gaps, spans: List[Event]) -> Dict[str, float]:
    """Idle seconds by the host span that covers most of each gap ("host:
    none" where no span of the harness was open)."""
    spans = sorted(spans, key=lambda e: e.start_ns)
    starts = [e.start_ns for e in spans]
    longest = max((e.dur_ns for e in spans), default=0.0)
    out: Dict[str, float] = {}
    for a, b in gaps:
        best, cover = "host: none", 0.0
        k = bisect.bisect_left(starts, b) - 1
        while k >= 0 and starts[k] > a - longest:
            s = spans[k]
            c = min(b, s.end_ns) - max(a, s.start_ns)
            if c > cover:
                best, cover = s.name, c
            k -= 1
        out[best] = out.get(best, 0.0) + (b - a) * 1e-9
    return out


def from_profile(path: str) -> List[Plane]:
    """Planes of the XSpace the profiler wrote under `path`."""
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True)
    if not files:
        return []
    planes = []
    for p in ProfileData.from_file(max(files, key=os.path.getmtime)).planes:
        lines = {ln.name: [Event(e.name, e.start_ns, e.duration_ns) for e in ln.events]
                 for ln in p.lines}
        planes.append(Plane(p.name, lines))
    return planes


@contextlib.contextmanager
def capture(path: str):
    """Profile the block: device ops, and the harness's own host spans."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 1      # TraceAnnotation spans, not every dispatch
    opts.python_tracer_level = 0
    jax.profiler.start_trace(path, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def span(name: str, on: bool):
    """A host span in the trace (a no-op when not tracing)."""
    if not on:
        return contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation(name)
