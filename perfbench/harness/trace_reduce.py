"""From a profiler trace to numbers: device busy and idle time, the
time of each device operation and program, and what the host was doing
in the idle gaps.

One reduction for every PR, kept with the benchmark and checked on a
small recorded trace (``perfbench/tests/data/recorded_trace.csv``).

What a TPU trace of this installation holds (jax 0.9.0, libtpu 0.0.34;
see ``describe``'s dump of a run under ``perfbench_out/``): one plane
per chip named ``/device:TPU:<n>``, with a line of HLO operations
(``XLA Ops``: a ``while`` contains the operations of its body, so
operation times here are SELF times) and a line of whole programs
(``XLA Modules``, named ``jit_<function>(<fingerprint>)``); host planes
``/host:...`` with one line per thread of TraceMe spans
(``PjitFunction(...)``, ``np.asarray(jax.Array)``, the benchmark's own
``TraceAnnotation`` spans). An operation's event is named by its whole
HLO instruction — ``%fusion.90 = (f32[]{...}, bf16[8,8,4,32]{...})
fusion(...), kind=kOutput, calls=...`` — and carries no category, so a
family is told by that text: the instruction's name, its opcode, its
fusion kind (on the TPU ``kOutput`` is a convolution or matrix product
with its epilogue) and its result's shape. ``label`` shortens it.
"""

from __future__ import annotations

import csv
import dataclasses
import re
from typing import Dict, Iterable, List, Tuple

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
CATEGORY_STATS = ("hlo_category", "category")
GAP_FLOOR_NS = 2_000       # gaps under 2 us are launch spacing, not idling
NAMED_GAPS = 500           # only the longest gaps are looked up on the host
UNNAMED_GAPS = "(shorter gaps, not named)"


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    dur_ns: float
    category: str = ""

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


_HLO = re.compile(r"^%(\S+) = (.*?) ([a-z][\w\-]*)\(")
_LAYOUT = re.compile(r"\{[^}]*\}")


def label(name: str) -> str:
    """``fusion.90 fusion/kOutput -> (f32[], bf16[8,8,4,32])`` from an
    HLO instruction's text; any other name as it is."""
    m = _HLO.match(name)
    if not m:
        return name
    op, result, opcode = m.groups()
    kind = re.search(r"kind=(\w+)", name)
    return "%s %s%s -> %s" % (
        op, opcode, "/" + kind.group(1) if kind else "",
        _LAYOUT.sub("", result),
    )


@dataclasses.dataclass
class DevicePlane:
    name: str
    ops: List[Event]
    modules: List[Event]


@dataclasses.dataclass
class Trace:
    devices: List[DevicePlane]
    host: List[Event]


# ---- loading -------------------------------------------------------------


def load_xplane(path: str) -> Trace:
    """Read an ``.xplane.pb`` with nothing but JAX."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host = [], []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {line.name: line for line in plane.lines}
            devices.append(DevicePlane(
                name=plane.name,
                ops=_events(lines.get(OPS_LINE), with_category=True),
                modules=_events(lines.get(MODULES_LINE)),
            ))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(_events(line))
    devices.sort(key=lambda d: d.name)
    return Trace(devices=devices, host=host)


def _events(line, with_category: bool = False) -> List[Event]:
    if line is None:
        return []
    out = []
    for e in line.events:
        if e.duration_ns <= 0:
            continue
        category = ""
        if with_category:
            for key, value in e.stats:
                if key in CATEGORY_STATS:
                    category = str(value)
                    break
        out.append(Event(e.name, float(e.start_ns), float(e.duration_ns),
                         category))
    return out


def load_csv(path: str) -> Trace:
    """A recorded trace as rows ``plane,line,name,start_ns,dur_ns,
    category``."""
    planes: Dict[str, DevicePlane] = {}
    host = []
    with open(path, newline="") as f:
        for row in csv.DictReader(f):
            ev = Event(row["name"], float(row["start_ns"]),
                       float(row["dur_ns"]), row.get("category", ""))
            if DEVICE_PLANE.match(row["plane"]):
                p = planes.setdefault(
                    row["plane"], DevicePlane(row["plane"], [], [])
                )
                if row["line"] == OPS_LINE:
                    p.ops.append(ev)
                elif row["line"] == MODULES_LINE:
                    p.modules.append(ev)
            else:
                host.append(ev)
    return Trace(devices=[planes[k] for k in sorted(planes)], host=host)


# ---- arithmetic ----------------------------------------------------------


def busy_intervals(events: Iterable[Event]) -> List[Tuple[float, float]]:
    """Union of the events' intervals, merged and sorted."""
    merged: List[List[float]] = []
    for e in sorted(events, key=lambda e: e.start_ns):
        if merged and e.start_ns <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e.end_ns)
        else:
            merged.append([e.start_ns, e.end_ns])
    return [(a, b) for a, b in merged]


def self_times(events: Iterable[Event]) -> List[Tuple[Event, float]]:
    """Each event's duration minus the part its children on the same
    line cover (a ``while`` contains its body's operations)."""
    order = sorted(events, key=lambda e: (e.start_ns, -e.dur_ns))
    selfs = [e.dur_ns for e in order]
    stack: List[int] = []
    for i, e in enumerate(order):
        while stack and order[stack[-1]].end_ns <= e.start_ns:
            stack.pop()
        if stack:
            selfs[stack[-1]] -= e.dur_ns
        stack.append(i)
    return [(e, max(s, 0.0)) for e, s in zip(order, selfs)]


def in_family(event: Event, family: dict) -> bool:
    """``family``: ``{"category": [substrings], "name": [regexes]}``.
    An operation belongs by the trace's HLO category where it carries
    one, or by its kind as its name shows it."""
    if event.category and any(
        c in event.category for c in family.get("category", [])
    ):
        return True
    return any(re.search(p, event.name) for p in family.get("name", []))


@dataclasses.dataclass
class Reduced:
    """What the per-layer readers take their numbers from."""

    chips: int
    window_s: float                    # first to last device operation
    busy_s: float                      # averaged over the chips
    op_self_s: Dict[str, float]        # by operation label, chip average
    op_events: List[Tuple[Event, float]]   # (event, self ns), all chips
    modules: Dict[str, List[float]]    # program name -> durations, s
    idle_gaps: List[Tuple[str, float]]     # by what the host did, s

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s if self.window_s else 0.0

    def family_self_s(self, family: dict) -> float:
        return sum(
            s for e, s in self.op_events if in_family(e, family)
        ) / 1e9 / max(self.chips, 1)

    def top_ops(self, n: int = 10) -> List[list]:
        top = sorted(self.op_self_s.items(), key=lambda kv: -kv[1])[:n]
        return [[name, seconds] for name, seconds in top]


def reduce_trace(trace: Trace) -> Reduced | None:
    """None when no operation ran on a device."""
    planes = [d for d in trace.devices if d.ops]
    if not planes:
        return None
    t0 = min(e.start_ns for d in planes for e in d.ops)
    t1 = max(e.end_ns for d in planes for e in d.ops)
    busy_ns, op_events, gaps = 0.0, [], []
    op_self: Dict[str, float] = {}
    modules: Dict[str, List[float]] = {}
    for d in planes:
        spans = busy_intervals(d.ops)
        busy_ns += sum(b - a for a, b in spans)
        edges = [t0] + [x for ab in spans for x in ab] + [t1]
        gaps += [
            (edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] - edges[i] > 0
        ]
        for e, s in self_times(d.ops):
            op_events.append((e, s))
            key = label(e.name)
            op_self[key] = op_self.get(key, 0.0) + s
        for m in d.modules:
            modules.setdefault(m.name, []).append(m.dur_ns / 1e9)
    n = len(planes)
    return Reduced(
        chips=n,
        window_s=(t1 - t0) / 1e9,
        busy_s=busy_ns / 1e9 / n,
        op_self_s={k: v / 1e9 / n for k, v in op_self.items()},
        op_events=op_events,
        modules=modules,
        idle_gaps=_name_gaps(gaps, trace.host, n),
    )


def _name_gaps(gaps, host: List[Event], chips: int, top: int = 10):
    """Idle seconds by the innermost host span that covers the middle
    of each gap; gaps under ``GAP_FLOOR_NS`` and gaps no span covers
    are summed apart."""
    by_name: Dict[str, float] = {}
    host = sorted(host, key=lambda e: e.dur_ns)  # innermost first
    gaps = sorted(gaps, key=lambda ab: ab[0] - ab[1])  # longest first
    for i, (a, b) in enumerate(gaps):
        name = UNNAMED_GAPS
        if i < NAMED_GAPS and b - a >= GAP_FLOOR_NS:
            mid = (a + b) / 2
            for e in host:
                if e.start_ns <= mid <= e.end_ns:
                    name = e.name
                    break
            else:
                name = "(no host span)"
        by_name[name] = by_name.get(name, 0.0) + (b - a)
    ranked = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
    return [[name, ns / 1e9 / chips] for name, ns in ranked]


def describe(trace: Trace, top: int = 25) -> dict:
    """A look at the trace by hand: which planes and lines there are and
    how the operations are named."""
    out = {"devices": [], "host_events": len(trace.host)}
    for d in trace.devices:
        names: Dict[str, list] = {}
        for e, s in self_times(d.ops):
            rec = names.setdefault(label(e.name), [0, 0.0, e.category])
            rec[0] += 1
            rec[1] += s
        ranked = sorted(names.items(), key=lambda kv: -kv[1][1])[:top]
        out["devices"].append({
            "plane": d.name, "ops": len(d.ops), "modules": len(d.modules),
            "module_names": sorted({m.name for m in d.modules})[:top],
            "top_ops": [
                {"name": k, "count": v[0], "self_s": v[1] / 1e9,
                 "category": v[2]} for k, v in ranked
            ],
        })
    hosts: Dict[str, list] = {}
    for e in trace.host:
        rec = hosts.setdefault(e.name, [0, 0.0])
        rec[0] += 1
        rec[1] += e.dur_ns
    out["top_host"] = [
        {"name": k, "count": v[0], "total_s": v[1] / 1e9}
        for k, v in sorted(hosts.items(), key=lambda kv: -kv[1][1])[:top]
    ]
    return out
