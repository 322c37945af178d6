"""The program's own measurement in a traced window: its named programs, its
name scopes and its host spans, on the device trace's clock.

From the profiler's XSpace it reads:

* the window: the host span `bench.window`, which the harness opens;
* host spans named `dictionary.*`: the facade's public methods
  (`api/dictionary.py`), `dictionary.update.prepare` nested in
  `dictionary.update`;
* per device (`/device:TPU:<n>` planes), the "XLA Modules" events named
  `jit__exec_<op>(<fingerprint>)`: the facade's programs; and the "XLA Ops"
  events with the name-scope path of each op (its `tf_op` stat, e.g.
  `jit(_exec_update)/lsm.stage/cond/branch_1_fun/lsm.push/sort`), of which
  `lsm.stage` and `lsm.push` mark the core's update path.

An op counts under the innermost of the scopes in `SCOPES` on its path, or
under "" when it has none. A device's events move later where some program
would otherwise start before its host dispatch (`PjitFunction(_exec_<op>)`),
by the least amount that ends that (`_causal_shift`). Idle intervals (no op running, inside the window)
split three ways: inside a facade program's interval; else inside a
`dictionary.*` span, while the facade's host code runs; else elsewhere
(launch, acknowledgement, the harness). Like `bench/trace.py`, the reduction
works on plain tuples, so a test can build a trace by hand; it keeps nothing
of the program, so a program without these names reads as nothing.

The per-layer metrics these numbers make (PERF.md §3) need the harness to
call `reduce(from_profile(<trace dir>))` inside its traced window's
temporary directory and to pass the result, with the difference of
`Dictionary.counters()` across the window, to the readers: an edit of
`bench/harness.py`, left to a benchmark change (PERF.md §7).
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import functools
import glob
import os
import re
from typing import Dict, List, NamedTuple, Optional, Tuple

from bench.trace import MODULES_LINE, OPS_LINE, WINDOW, Event, Plane, _self_times, _union

SPAN_PREFIX = "dictionary."
SCOPES = ("lsm.stage", "lsm.push")
SCOPE_STAT = "tf_op"
_DEVICE = re.compile(r"^/device:TPU:\d+$")
_MODULE = re.compile(r"^jit_(_exec_\w+?)(?:\(.*\))?$")
_DISPATCH = re.compile(r"^PjitFunction\((_exec_\w+)\)$")

Interval = Tuple[float, float]


class Op(NamedTuple):
    name: str
    start_ns: float
    dur_ns: float
    scope: str  # the op's name-scope path; "" where the trace has none

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class DeviceProgram:
    scope_s: Dict[Tuple[str, str], float]  # (program, scope) -> op self seconds
    module_s: Dict[str, float]             # program -> seconds of its intervals
    idle_in_programs_s: float
    idle_in_facade_s: float
    shift_s: float  # how much later its events were moved (`_causal_shift`)


@dataclasses.dataclass
class ProgramTrace:
    window_s: float
    devices: List[DeviceProgram]
    spans_s: Dict[str, float]  # host span name -> seconds inside the window

    def _mean(self, per_device) -> float:
        return sum(per_device(d) for d in self.devices) / len(self.devices)

    def scope_s(self, scope: str, *programs: str) -> float:
        """Op self seconds under `scope` ("" for none) in these programs,
        averaged over devices."""
        return self._mean(lambda d: sum(d.scope_s.get((p, scope), 0.0) for p in programs))

    def module_s(self, *programs: str) -> float:
        """Seconds in these programs, averaged over devices."""
        return self._mean(lambda d: sum(d.module_s.get(p, 0.0) for p in programs))

    @property
    def idle_in_programs_s(self) -> float:
        return self._mean(lambda d: d.idle_in_programs_s)

    @property
    def idle_in_facade_s(self) -> float:
        return self._mean(lambda d: d.idle_in_facade_s)


@functools.lru_cache(maxsize=None)
def scope_of(path: str) -> str:
    """The innermost of `SCOPES` on a name-scope path, or ""."""
    best, at = "", -1
    for k, part in enumerate(path.split("/")):
        if part in SCOPES:
            best, at = part, k
    return best if at >= 0 else ""


def _intersect(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """Intersection of two sorted lists of disjoint intervals."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if lo < hi:
            out.append((lo, hi))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def _subtract(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """Parts of the sorted disjoint intervals `a` outside those of `b`."""
    out, j = [], 0
    for lo, hi in a:
        while j < len(b) and b[j][1] <= lo:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > lo:
                out.append((lo, b[k][0]))
            lo = max(lo, b[k][1])
            k += 1
        if lo < hi:
            out.append((lo, hi))
    return out


def _length(intervals: List[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def _program(module_name: str) -> Optional[str]:
    """`_exec_update` from `jit__exec_update(123)`; None for other modules."""
    m = _MODULE.match(module_name)
    return m.group(1) if m else None


def _dispatch_starts(host: List[Event]) -> Dict[str, List[float]]:
    """Program -> start of each of its dispatches, from the host's
    `PjitFunction(_exec_<op>)` events; one call records two, nested."""
    starts: Dict[str, List[float]] = collections.defaultdict(list)
    ends: Dict[str, float] = {}
    for e in sorted(host, key=lambda e: e.start_ns):
        m = _DISPATCH.match(e.name)
        if m and e.start_ns >= ends.get(m.group(1), float("-inf")):
            starts[m.group(1)].append(e.start_ns)
            ends[m.group(1)] = e.end_ns
    return starts


def _causal_shift(modules: List[Event], dispatches: Dict[str, List[float]]) -> float:
    """Nanoseconds by which a device's events must move later so that no
    facade program starts before its host dispatch began: the k-th
    `jit__exec_<op>` module of the device runs the k-th `PjitFunction(
    _exec_<op>)` dispatch of the trace. The trace places device and host
    events on one clock only to within a fraction of a millisecond, and a
    run can show programs starting before their dispatch; the least shift
    that ends that is taken (0 where none is needed, or where the counts
    differ and the pairing is unknown)."""
    starts: Dict[str, List[float]] = collections.defaultdict(list)
    for e in modules:
        name = _program(e.name)
        if name:
            starts[name].append(e.start_ns)
    lead = 0.0
    for name, ms in starts.items():
        ds = dispatches.get(name, [])
        if len(ds) == len(ms):
            lead = max([lead] + [d - m for d, m in zip(sorted(ds), sorted(ms))])
    return lead


def reduce(planes: List[Plane]) -> Optional[ProgramTrace]:
    """The program's numbers in the traced window, or None when the trace has
    no window or no device ran anything in it."""
    host = [e for p in planes if not _DEVICE.match(p.name)
            for events in p.lines.values() for e in events]
    dispatches = _dispatch_starts(host)
    windows = [e for e in host if e.name == WINDOW]
    if not windows:
        return None
    w0, w1 = windows[0].start_ns, windows[0].end_ns

    def clip(events) -> List[Interval]:
        return [(max(e.start_ns, w0), min(e.end_ns, w1)) for e in events
                if e.end_ns > w0 and e.start_ns < w1]

    spans = [e for e in host if e.name.startswith(SPAN_PREFIX)
             and e.end_ns > w0 and e.start_ns < w1]
    spans_s: Dict[str, float] = {}
    for e, (a, b) in zip(spans, clip(spans)):
        spans_s[e.name] = spans_s.get(e.name, 0.0) + (b - a) * 1e-9
    facade = _union(clip(spans))
    devices, busy_s = [], 0.0
    for p in sorted((p for p in planes if _DEVICE.match(p.name)), key=lambda p: p.name):
        shift = _causal_shift(p.lines.get(MODULES_LINE, []), dispatches)

        def later(events):
            return [e._replace(start_ns=e.start_ns + shift) for e in events] if shift else events

        ops = sorted((e for e in later(p.lines.get(OPS_LINE, []))
                      if e.end_ns > w0 and e.start_ns < w1),
                     key=lambda e: (e.start_ns, -e.end_ns))
        modules = sorted(((e, _program(e.name)) for e in later(p.lines.get(MODULES_LINE, []))
                          if e.end_ns > w0 and e.start_ns < w1 and _program(e.name)),
                         key=lambda m: m[0].start_ns)
        clipped = clip(ops)
        busy = _union(clipped)
        busy_s += _length(busy)
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        gaps = [(edges[k], edges[k + 1]) for k in range(0, len(edges), 2)
                if edges[k + 1] > edges[k]]
        module_iv = clip([e for e, _ in modules])
        in_programs = _union(module_iv)
        module_s: Dict[str, float] = {}
        for (_, name), (a, b) in zip(modules, module_iv):
            module_s[name] = module_s.get(name, 0.0) + (b - a) * 1e-9
        scope_s: Dict[Tuple[str, str], float] = {}
        k = 0
        for op, own in zip(ops, _self_times(clipped)):
            while k + 1 < len(modules) and modules[k + 1][0].start_ns <= op.start_ns:
                k += 1
            inside = modules and modules[k][0].start_ns <= op.start_ns < modules[k][0].end_ns
            key = (modules[k][1] if inside else "?", scope_of(op.scope))
            scope_s[key] = scope_s.get(key, 0.0) + own * 1e-9
        devices.append(DeviceProgram(
            scope_s, module_s,
            _length(_intersect(gaps, in_programs)) * 1e-9,
            _length(_intersect(_subtract(gaps, in_programs), facade)) * 1e-9,
            shift * 1e-9))
    if busy_s <= 0:
        return None
    return ProgramTrace((w1 - w0) * 1e-9, devices, spans_s)


def from_profile(path: str) -> List[Plane]:
    """Planes of the XSpace the profiler wrote under `path`: the host spans
    (window and facade), and per device its modules and its ops with their
    name-scope paths. An op without a `tf_op` stat (the TPU trace gives
    none) takes the `op_name` of its instruction in the HLO of the module
    running when it started (`hlo_op_names`)."""
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True)
    if not files:
        return []
    file = max(files, key=os.path.getmtime)
    op_names = None
    planes = []
    for p in ProfileData.from_file(file).planes:
        lines: Dict[str, list] = {}
        if _DEVICE.match(p.name):
            by_line = {ln.name: ln for ln in p.lines}
            modules = sorted((Event(e.name, e.start_ns, e.duration_ns)
                              for e in by_line[MODULES_LINE].events)
                             if MODULES_LINE in by_line else [], key=lambda e: e.start_ns)
            starts = [e.start_ns for e in modules]
            ops = []
            for e in by_line[OPS_LINE].events if OPS_LINE in by_line else []:
                scope = dict(e.stats).get(SCOPE_STAT)
                if scope is None:
                    if op_names is None:
                        op_names = hlo_op_names(file)
                    k = bisect.bisect_right(starts, e.start_ns) - 1
                    name = modules[k].name if k >= 0 else ""
                    module = op_names.get(name) or op_names.get(name.split("(")[0], {})
                    scope = module.get(_instruction(e.name), "")
                ops.append(Op(e.name, e.start_ns, e.duration_ns, str(scope)))
            lines = {OPS_LINE: ops, MODULES_LINE: modules}
        else:
            for ln in p.lines:
                kept = [Event(e.name, e.start_ns, e.duration_ns) for e in ln.events
                        if e.name == WINDOW or e.name.startswith(SPAN_PREFIX)
                        or _DISPATCH.match(e.name)]
                if kept:
                    lines[ln.name] = kept
        planes.append(Plane(p.name, lines))
    return planes


def _instruction(op_event_name: str) -> str:
    """`sort.6` from an op event named `%sort.6 = (s32[...]) sort(...)`."""
    return op_event_name.lstrip("%").split(" ", 1)[0]


# -- the HLO the profiler keeps: plane `/host:metadata`, one event metadata per
# loaded module (named as its "XLA Modules" events), whose stat "Hlo Proto"
# holds an xla.HloProto. Field numbers from tsl's xplane.proto and xla's
# hlo.proto; only what is needed is decoded.
_XSPACE_PLANES, _PLANE_NAME, _PLANE_EVENT_METADATA, _PLANE_STAT_METADATA = 1, 2, 4, 5
_EVENT_METADATA_NAME, _EVENT_METADATA_STATS = 2, 5
_STAT_METADATA_ID, _STAT_BYTES = 1, 6
_HLO_MODULE, _MODULE_COMPUTATIONS, _COMPUTATION_INSTRUCTIONS = 1, 3, 2
_INSTRUCTION_NAME, _INSTRUCTION_METADATA, _METADATA_OP_NAME = 1, 7, 2


def _varint(buf, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of one protobuf message: an int for a varint, a
    memoryview for a length-delimited field; fixed-width fields skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
            continue
        else:
            raise ValueError(f"protobuf wire type {wire} not read here")
        yield key >> 3, value


def _first(buf, field: int, default=None):
    return next((v for f, v in _fields(buf) if f == field), default)


def _map_values(plane, field: int):
    """Values of a protobuf map field (entries: key = 1, value = 2)."""
    return [_first(entry, 2, b"") for f, entry in _fields(plane) if f == field]


def hlo_op_names(file: str) -> Dict[str, Dict[str, str]]:
    """Module name (`jit__exec_update(<id>)`) -> {instruction name -> its
    `op_name` metadata, the name scopes it ran under}, from the HLO that the
    XSpace file keeps of every module loaded while it traced."""
    with open(file, "rb") as f:
        space = memoryview(f.read())
    out: Dict[str, Dict[str, str]] = {}
    for field, plane in _fields(space):
        if field != _XSPACE_PLANES or bytes(_first(plane, _PLANE_NAME, b"")) != b"/host:metadata":
            continue
        hlo = {_first(sm, _STAT_METADATA_ID, 0) for sm in _map_values(plane, _PLANE_STAT_METADATA)
               if bytes(_first(sm, 2, b"")) == b"Hlo Proto"}
        for em in _map_values(plane, _PLANE_EVENT_METADATA):
            name = bytes(_first(em, _EVENT_METADATA_NAME, b"")).decode()
            for f_, stat in _fields(em):
                if f_ == _EVENT_METADATA_STATS and _first(stat, _STAT_METADATA_ID, 0) in hlo:
                    out[name] = _op_names(_first(stat, _STAT_BYTES, b""))
    # Also under the bare module name where one module has it, should the
    # trace's module events carry another suffix than the metadata.
    bare = collections.Counter(name.split("(")[0] for name in out)
    out.update({n.split("(")[0]: v for n, v in list(out.items()) if bare[n.split("(")[0]] == 1})
    return out


def _op_names(hlo_proto) -> Dict[str, str]:
    names = {}
    module = _first(hlo_proto, _HLO_MODULE, b"")
    for f, comp in _fields(module):
        if f != _MODULE_COMPUTATIONS:
            continue
        for g, inst in _fields(comp):
            if g != _COMPUTATION_INSTRUCTIONS:
                continue
            name, op_name = "", ""
            for h, v in _fields(inst):
                if h == _INSTRUCTION_NAME:
                    name = bytes(v).decode()
                elif h == _INSTRUCTION_METADATA:
                    op_name = bytes(_first(v, _METADATA_OP_NAME, b"")).decode()
            names[name] = op_name
    return names
