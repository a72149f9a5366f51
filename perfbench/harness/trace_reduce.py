"""From a profiler trace to device intervals: the reduction every per-layer
reader shares, kept here so that every PR computes the same numbers the same
way.

``load`` turns the profiler's ``.xplane.pb`` into a ``Trace``: per device the
operations of its "XLA Ops" line (name, HLO opcode, name path, start, end), the
start-to-done intervals of its "Async XLA Ops" line, and the host's ``bench/*``
spans on the same clock.  Events come from JAX's own reader; the name path
(``jit(pretrain_step)/jvp(LlamaLMModel)/h_0/attn/wq/dot_general`` — the flax
module scopes) is a stat of the event's *metadata*, which that reader does not
show, so ``_name_paths`` takes it from the file's bytes with a protobuf wire
reader of its own (``xplane.proto`` of TSL).  Everything below ``load`` is plain
arithmetic on intervals and is tested on a recorded trace
(``perfbench/fixtures``): the busy union, self times under nesting (a ``while``
contains its body's operations), idle gaps and the host span that covered
each, and the part of a set of intervals that no other operation overlaps.
"""

from __future__ import annotations

import json
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]          # (start, end), seconds

OPS_LINE, ASYNC_LINE = "XLA Ops", "Async XLA Ops"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
SPAN_PREFIX = "bench/"
PATH_STAT = "tf_op"     # the metadata stat that holds the name path
# an event of the ops lines is named by its whole HLO instruction:
# "%attn.4 = (bf16[...], f32[...]) custom-call(...), custom_call_target=..."
_INSTRUCTION = re.compile(r"^%?(\S+) = .*? ([a-z][a-z0-9\-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


@dataclass(frozen=True)
class Op:
    name: str       # the HLO instruction's name: "fusion.539", "attn.4"
    kind: str       # its opcode: "fusion", "while", "all-gather-start",
                    # "custom-call:tpu_custom_call" (with the call's target)
    path: str       # its name path (module scopes), "" where the trace has none
    start: float
    end: float

    @property
    def seconds(self) -> float:
        return self.end - self.start

    @property
    def label(self) -> str:
        """What a reader's ``op`` pattern is matched against."""
        return f"{self.kind} {self.name}"


@dataclass
class Trace:
    ops: Dict[int, List[Op]] = field(default_factory=dict)   # by device
    spans: List[Tuple[str, float, float]] = field(default_factory=list)
    # start-to-done of asynchronous operations (copies, slices, collectives)
    async_ops: Dict[int, List[Op]] = field(default_factory=dict)

    def window(self, name: str = "window") -> Optional[Interval]:
        found = [(a, b) for n, a, b in self.spans if n == name]
        return found[0] if found else None

    def clipped(self, window: Interval) -> "Trace":
        """Only what lies inside ``window``, cut at its edges."""
        lo, hi = window
        cut = lambda a, b: (max(a, lo), min(b, hi))  # noqa: E731
        clip = lambda by_device: {  # noqa: E731
            d: [Op(o.name, o.kind, o.path, *cut(o.start, o.end)) for o in ops
                if o.end > lo and o.start < hi]
            for d, ops in by_device.items()}
        return Trace(
            clip(self.ops),
            [(n, *cut(a, b)) for n, a, b in self.spans if b > lo and a < hi],
            clip(self.async_ops))

    # a recorded trace as a fixture: plain JSON, no profiler needed to read it
    def to_json(self) -> str:
        rows = lambda by_device: {  # noqa: E731
            str(d): [[o.name, o.kind, o.path, o.start, o.end] for o in ops]
            for d, ops in by_device.items()}
        return json.dumps({"ops": rows(self.ops), "spans": self.spans,
                           "async_ops": rows(self.async_ops)})

    @staticmethod
    def from_json(text: str) -> "Trace":
        raw = json.loads(text)
        ops = lambda rows: {int(d): [Op(*o) for o in v]  # noqa: E731
                            for d, v in rows.items()}
        return Trace(ops(raw["ops"]), [tuple(s) for s in raw["spans"]],
                     ops(raw.get("async_ops", {})))


def parse_instruction(text: str) -> Tuple[str, str]:
    """(name, kind) of an ops-line event's name."""
    m = _INSTRUCTION.match(text)
    if not m:
        return text, ""
    name, kind = m.groups()
    if kind == "custom-call":
        target = _TARGET.search(text)
        kind += ":" + (target.group(1) if target else "")
    return name, kind


def load(xplane_path: str) -> Trace:
    """Read the profiler's file: events with JAX's own reader, name paths
    from the metadata."""
    from jax.profiler import ProfileData

    with open(xplane_path, "rb") as f:
        xspace = f.read()
    paths = _name_paths(xspace)
    trace = Trace()
    for plane in ProfileData.from_serialized_xspace(xspace).planes:
        device = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if device and line.name in (OPS_LINE, ASYNC_LINE):
                into = trace.ops if line.name == OPS_LINE else trace.async_ops
                ops = into.setdefault(int(device.group(1)), [])
                known = paths.get(plane.name, {})
                for e in line.events:
                    ops.append(Op(*parse_instruction(e.name),
                                  known.get(e.name, ""), e.start_ns * 1e-9,
                                  (e.start_ns + e.duration_ns) * 1e-9))
            elif plane.name == "/host:CPU":
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        trace.spans.append(
                            (e.name[len(SPAN_PREFIX):], e.start_ns * 1e-9,
                             (e.start_ns + e.duration_ns) * 1e-9))
    for by_device in (trace.ops, trace.async_ops):
        for ops in by_device.values():
            ops.sort(key=lambda o: (o.start, -o.end))
    trace.spans.sort(key=lambda s: s[1])
    return trace


# ---------------------------------------------------- protobuf wire reader
def _fields(buf: bytes):
    """(field number, wire type, value) of one message; a length-delimited
    value comes as bytes, a varint as an int, fixed widths are skipped."""
    i, n = 0, len(buf)
    while i < n:
        key = shift = 0
        while True:
            b = buf[i]
            i += 1
            key |= (b & 0x7F) << shift
            shift += 7
            if b < 0x80:
                break
        number, wire = key >> 3, key & 7
        if wire == 0:
            value = shift = 0
            while True:
                b = buf[i]
                i += 1
                value |= (b & 0x7F) << shift
                shift += 7
                if b < 0x80:
                    break
            yield number, wire, value
        elif wire == 2:
            size = shift = 0
            while True:
                b = buf[i]
                i += 1
                size |= (b & 0x7F) << shift
                shift += 7
                if b < 0x80:
                    break
            yield number, wire, buf[i:i + size]
            i += size
        elif wire == 1:
            i += 8
        elif wire == 5:
            i += 4
        else:
            raise ValueError(f"wire type {wire} in an xplane file")


def _name_paths(xspace: bytes) -> Dict[str, Dict[str, str]]:
    """plane name -> event name -> name path.  XSpace.planes = 1; XPlane.name
    = 2, .event_metadata = 4 and .stat_metadata = 5 (maps: key 1, value 2);
    XEventMetadata.name = 2, .stats = 5; XStatMetadata.name = 2;
    XStat.metadata_id = 1, .str_value = 5.  Lines (3) are skipped unread."""
    out: Dict[str, Dict[str, str]] = {}
    for number, _, plane in _fields(xspace):
        if number != 1:
            continue
        name, events, stat_names = "", [], {}
        for number, _, value in _fields(plane):
            if number == 2:
                name = value.decode()
            elif number in (4, 5):
                entry = {k: v for k, _, v in _fields(value)}
                if number == 5:
                    stat_names[entry[1]] = next(
                        (v.decode() for k, _, v in _fields(entry[2])
                         if k == 2), "")
                else:
                    events.append(entry[2])
        wanted = {i for i, n in stat_names.items() if n == PATH_STAT}
        if not DEVICE_PLANE.match(name) or not wanted:
            continue
        known = out.setdefault(name, {})
        for meta in events:
            event_name, path = "", ""
            for number, _, value in _fields(meta):
                if number == 2:
                    event_name = value.decode(errors="replace")
                elif number == 5:
                    stat = {k: v for k, _, v in _fields(value)}
                    if stat.get(1) in wanted and isinstance(stat.get(5), bytes):
                        path = stat[5].decode(errors="replace").rstrip(":")
            if path:
                known[event_name] = path
    return out


# ------------------------------------------------------------- arithmetic
def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Disjoint, sorted intervals covering the same set."""
    out: List[List[float]] = []
    for a, b in sorted(i for i in intervals if i[1] > i[0]):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def total(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def busy_seconds(ops: Sequence[Op]) -> float:
    return total(union((o.start, o.end) for o in ops))


def intersect(xs: Sequence[Interval], ys: Sequence[Interval]
              ) -> List[Interval]:
    """Intersection of two disjoint sorted interval lists."""
    out, i, j = [], 0, 0
    while i < len(xs) and j < len(ys):
        lo, hi = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if hi > lo:
            out.append((lo, hi))
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return out


def subtract(xs: Sequence[Interval], ys: Sequence[Interval]
             ) -> List[Interval]:
    """The part of ``xs`` (disjoint, sorted) that ``ys`` does not cover."""
    out = []
    ys = list(ys)
    for a, b in xs:
        for c, d in ys:
            if d <= a or c >= b:
                continue
            if c > a:
                out.append((a, c))
            a = max(a, d)
            if a >= b:
                break
        if a < b:
            out.append((a, b))
    return out


def self_seconds(ops: Sequence[Op]) -> List[Tuple[Op, float]]:
    """Each operation with the time no operation nested inside it covers
    (``ops`` sorted by start, longer first on ties).  A ``while`` keeps only
    what its body leaves over."""
    out: List[List] = []
    stack: List[int] = []
    for o in ops:
        while stack and out[stack[-1]][0].end <= o.start:
            stack.pop()
        if stack and o.end <= out[stack[-1]][0].end:
            out[stack[-1]][1] -= o.seconds
        out.append([o, o.seconds])
        stack.append(len(out) - 1)
    return [(o, max(s, 0.0)) for o, s in out]


def leaves(ops: Sequence[Op]) -> List[Op]:
    """The operations that contain no other operation."""
    out: List[Op] = []
    ops = list(ops)
    for i, o in enumerate(ops):
        nxt = ops[i + 1] if i + 1 < len(ops) else None
        if nxt is None or not (nxt.start >= o.start and nxt.end <= o.end
                               and nxt.seconds < o.seconds):
            out.append(o)
    return out


def gaps(ops: Sequence[Op], window: Interval) -> List[Interval]:
    """The idle intervals of one device inside ``window``."""
    return subtract([window], union((o.start, o.end) for o in ops))


def label_gaps(idle: Sequence[Interval],
               spans: Sequence[Tuple[str, float, float]],
               fallback: str = "none") -> Dict[str, float]:
    """Idle seconds by the host span that covered them; the innermost span
    wins where spans nest, and ``fallback`` takes what no span covers."""
    out: Dict[str, float] = {}
    idle = union(idle)
    # innermost first: shorter spans claim their part before longer ones
    for name, a, b in sorted(spans, key=lambda s: s[2] - s[1]):
        if name == "window":
            continue
        got = intersect(idle, [(a, b)])
        if got:
            out[name] = out.get(name, 0.0) + total(got)
            idle = subtract(idle, [(a, b)])
    rest = total(idle)
    if rest > 0:
        out[fallback] = out.get(fallback, 0.0) + rest
    return out


def exposed_seconds(these: Sequence[Interval], others: Sequence[Interval]
                    ) -> float:
    """The part of ``these`` during which none of ``others`` runs."""
    return total(subtract(union(these), union(others)))


def async_intervals(ops: Sequence[Op], pattern: str) -> List[Interval]:
    """Intervals of the operations whose label matches ``pattern``, from an
    "XLA Ops" line (the "Async XLA Ops" line, where the trace has one, gives
    start-to-done directly); an asynchronous pair
    (``<kind>-start`` ... ``<kind>-done``) counts from the start's beginning
    to the done's end, pairing each done with the oldest open start of its
    kind."""
    rx = re.compile(pattern)
    open_starts: Dict[str, List[float]] = {}
    out: List[Interval] = []
    for o in ops:
        if not rx.search(o.label):
            continue
        m = re.match(r"(.*?)-(start|done)$", o.kind)
        if not m:
            out.append((o.start, o.end))
        elif m.group(2) == "start":
            open_starts.setdefault(m.group(1), []).append(o.start)
        elif open_starts.get(m.group(1)):
            out.append((open_starts[m.group(1)].pop(0), o.end))
        else:
            out.append((o.start, o.end))
    return out
