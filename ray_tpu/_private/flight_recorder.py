"""Crash-surviving per-process flight recorder (the "black box").

A SIGKILL'd worker takes its in-memory task-event buffer with it: the last
thing the cluster knows about the victim is whatever it last flushed, which
for a rank that died mid-allreduce is usually nothing.  This module keeps a
small mmap'd ring file in the session directory that hot paths append
fixed-framing records into with *no syscall per record* — the kernel owns
the dirty pages and writes them back whether or not the process survives,
so the last N seconds of activity are readable post-mortem by anyone who
can open the file (the nodelet harvests it in ``_handle_worker_death``).

Ring layout (all little-endian)::

    header (32 B):  b"RTFR" | u32 version | u32 capacity | u32 pad
                    | u64 write-cursor | u64 next-seq
    record:         u32 0xF17EC0DE | u32 payload-len | u64 seq | f64 ts
                    | payload ("kind|detail", utf-8)

Records never straddle the wrap point: when the tail of the data region is
too small for the next record it is zero-filled and the cursor wraps, so a
harvester can self-synchronize by scanning for the record magic and
validating the frame (length bound, utf-8 payload, finite timestamp).  The
monotonically increasing ``seq`` orders harvested records and exposes gaps.

Enabled per-process by :func:`init_process` (core workers and nodelets call
it at startup); sized by the ``flight_recorder_bytes`` flag (0 disables).
Call sites guard with ``if flight_recorder.RECORDING:`` so a disabled
recorder costs one module-attribute check.

Timed marks.  A record whose detail starts with a duration —
``<seconds>|<rest>`` — is an interval that *ends* at the record's stamp
(:func:`mark`, and :func:`timed` around a block).  Every stamp is
``time.time()``, the one clock all processes of a host share, so the marks
of a driver, a nodelet and a worker nest by time though no span crosses a
process.  A job's start is written this way (kinds ``bringup.*``: the table
is in docs/ARCHITECTURE.md 5e) and so are the train worker's compile events
(kind ``compile``); :func:`bringup_timeline` reads them back from every ring
of a session.  Marks made before a process has its ring (a driver before
its core worker, a worker before its own) wait in a short list and are
written, with their own stamps, by :func:`init_process`.

A mark made through :func:`timed` or :func:`mark_since` ends its detail with
what the interval cost — ``cpu=<s> majflt=<n> inblock=<n>``: the calling
thread's CPU seconds and the process's major page faults and blocks read from
a disk — so that seconds that were not computed in can be told from seconds
waited for the disk or for something else.  :func:`bringup_timeline` takes
that field off again; :func:`start_account` reads it.
"""

from __future__ import annotations

import collections
import contextlib
import glob
import math
import mmap
import os
import resource
import statistics
import struct
import threading
import time
from typing import Any, Dict, Iterator, List, NamedTuple, Optional, Tuple

FILE_MAGIC = b"RTFR"
VERSION = 1
HEADER = struct.Struct("<4sIII QQ")  # magic, version, capacity, pad, cursor, seq
REC_MAGIC = 0xF17EC0DE
REC_HEAD = struct.Struct("<IIQd")  # magic, payload len, seq, ts
MAX_PAYLOAD = 512  # oversized details are truncated, never split

RECORDING = False  # hot-path guard: one module-attribute check when off

_lock = threading.Lock()
_mm: Optional[mmap.mmap] = None
_capacity = 0
_cursor = 0  # offset into the data region (after the header)
_seq = 0
_path: Optional[str] = None
_m_records = None
# marks made before this process has a ring: (kind, detail, ts), oldest
# first, written by init_process; bounded, so a process that never opens a
# ring (recorder off, a plain script) keeps a few hundred bytes
_pending: List[Tuple[str, str, float]] = []
_PENDING_MAX = 64

# A mark: (process name, kind, start, end, detail), times on time.time()
Mark = Tuple[str, str, float, float, str]
ENTERED = "bringup.worker.train_fn_enter"
FIRST_REPORT = "bringup.first_report"   # a point: where a start ends
# where a process is in time and in what it has used: perf_counter, the
# thread's CPU seconds, the process's major faults and blocks read
Usage = Tuple[float, float, int, int]


def ring_path(session_dir: str, name: str) -> str:
    """Where a process named ``name`` keeps its ring under ``session_dir``."""
    return os.path.join(session_dir, "blackbox", f"{name}.ring")


def init_process(session_dir: str, name: str) -> bool:
    """Open (creating) this process's ring file and start recording.

    Idempotent; returns whether recording is on.  A ``flight_recorder_bytes``
    of 0 — or any OS error creating the file — leaves the recorder off:
    observability must never take the process down.
    """
    global RECORDING, _mm, _capacity, _cursor, _seq, _path, _m_records
    from ray_tpu._private.config import RayConfig

    size = int(RayConfig.flight_recorder_bytes)
    if size <= 0 or not session_dir:
        if _mm is None:
            _pending.clear()    # off: what waited for a ring is dropped
        return RECORDING
    with _lock:
        if _mm is not None:
            return RECORDING
        size = max(size, HEADER.size + REC_HEAD.size + MAX_PAYLOAD)
        path = ring_path(session_dir, name)
        try:
            os.makedirs(os.path.dirname(path), exist_ok=True)
            fd = os.open(path, os.O_CREAT | os.O_RDWR | os.O_TRUNC, 0o644)
            try:
                os.ftruncate(fd, size)
                _mm = mmap.mmap(fd, size)
            finally:
                os.close(fd)
        except OSError:
            return RECORDING
        _capacity = size - HEADER.size
        _cursor = 0
        _seq = 0
        _path = path
        HEADER.pack_into(_mm, 0, FILE_MAGIC, VERSION, _capacity, 0, 0, 0)
        if _m_records is None:
            from ray_tpu._private import metrics as M

            _m_records = M.Counter(
                "blackbox_records_total",
                "flight-recorder records appended to this process's "
                "crash-surviving ring file, by record kind")
        RECORDING = True
        waited, _pending[:] = list(_pending), []
    for kind, detail, ts in waited:
        record(kind, detail, ts)
    record("recorder.init", name)
    return True


def record(kind: str, detail: str = "", ts: Optional[float] = None) -> None:
    """Append one record, stamped ``ts`` (default: now).  Pure memory writes
    into the mmap — the kernel flushes the dirty page on its own schedule
    (and at process death), so the hot path never issues a syscall."""
    global _cursor, _seq
    mm = _mm
    if mm is None:
        return
    payload = f"{kind}|{detail}".encode("utf-8", "replace")[:MAX_PAYLOAD]
    need = REC_HEAD.size + len(payload)
    if ts is None:
        ts = time.time()
    with _lock:
        if _mm is None:  # closed between the guard and the lock
            return
        if _cursor + need > _capacity:
            # zero the tail so a stale record there cannot be harvested,
            # then wrap: records never straddle the boundary
            mm[HEADER.size + _cursor:HEADER.size + _capacity] = \
                b"\x00" * (_capacity - _cursor)
            _cursor = 0
        _seq += 1
        off = HEADER.size + _cursor
        REC_HEAD.pack_into(mm, off, REC_MAGIC, len(payload), _seq, ts)
        mm[off + REC_HEAD.size:off + need] = payload
        _cursor += need
        HEADER.pack_into(mm, 0, FILE_MAGIC, VERSION, _capacity, 0,
                         _cursor, _seq)
    if _m_records is not None:
        _m_records.inc(1, {"kind": kind})


def mark(kind: str, seconds: float, detail: str = "",
         ts: Optional[float] = None) -> None:
    """One timed record: an interval of ``seconds`` that ends at ``ts``
    (default: now), the duration first in the detail.  Before this process
    has a ring the mark waits (see the module docstring)."""
    if ts is None:
        ts = time.time()
    detail = f"{seconds:.6f}|{detail}" if detail else f"{seconds:.6f}"
    if _mm is None:
        if len(_pending) < _PENDING_MAX:
            _pending.append((kind, detail, ts))
        return
    record(kind, detail, ts)


def usage() -> Usage:
    """Where the calling thread stands: what :func:`mark_since` measures
    from."""
    used = resource.getrusage(resource.RUSAGE_SELF)
    return (time.perf_counter(), time.thread_time(), used.ru_majflt,
            used.ru_inblock)


def mark_since(kind: str, since: Usage, detail: str = "") -> None:
    """One :func:`mark` of the interval from ``since`` (a :func:`usage` of
    this thread) to now, its cost behind the detail."""
    t0, cpu0, faults0, blocks0 = since
    t1, cpu1, faults1, blocks1 = usage()
    cost = (f"cpu={cpu1 - cpu0:.6f} majflt={faults1 - faults0} "
            f"inblock={blocks1 - blocks0}")
    mark(kind, t1 - t0, f"{detail}|{cost}" if detail else cost)


@contextlib.contextmanager
def timed(kind: str, detail: str = "") -> Iterator[None]:
    """Time the block and write one :func:`mark_since` at its exit — also
    where the block raises: a phase that failed is the one an operator looks
    for."""
    since = usage()
    try:
        yield
    finally:
        mark_since(kind, since, detail)


def shutdown() -> None:
    """Close the ring (a driver's ``shutdown()``, tests; a real crash is the
    point of not needing this).  The file stays on disk for harvest."""
    global RECORDING, _mm, _path
    with _lock:
        RECORDING = False
        _pending.clear()
        if _mm is not None:
            try:
                _mm.close()
            except (BufferError, ValueError):
                pass
        _mm = None
        _path = None


def harvest(path: str, limit: Optional[int] = None) -> List[Dict]:
    """Parse a ring file (typically a dead process's) into ordered records.

    Self-synchronizing: scans the data region for the record magic and
    keeps frames that validate (bounded length, finite timestamp, utf-8
    payload), so a torn write at the crash point costs at most that one
    record.  Returns ``[{"seq", "ts", "kind", "detail"}, ...]`` sorted by
    seq; ``limit`` keeps only the newest N.
    """
    try:
        with open(path, "rb") as f:
            buf = f.read()
    except OSError:
        return []
    if len(buf) <= HEADER.size or buf[:4] != FILE_MAGIC:
        return []
    data = buf[HEADER.size:]
    out: Dict[int, Dict] = {}
    pos = 0
    magic_bytes = struct.pack("<I", REC_MAGIC)
    while True:
        pos = data.find(magic_bytes, pos)
        if pos < 0 or pos + REC_HEAD.size > len(data):
            break
        _, plen, seq, ts = REC_HEAD.unpack_from(data, pos)
        end = pos + REC_HEAD.size + plen
        if plen > MAX_PAYLOAD or end > len(data) or seq == 0 \
                or not math.isfinite(ts):
            pos += 1  # false sync: resume the scan one byte later
            continue
        try:
            payload = data[pos + REC_HEAD.size:end].decode("utf-8")
        except UnicodeDecodeError:
            pos += 1
            continue
        kind, _, detail = payload.partition("|")
        out[seq] = {"seq": seq, "ts": ts, "kind": kind, "detail": detail}
        pos = end
    rows = [out[s] for s in sorted(out)]
    if limit is not None and len(rows) > limit:
        rows = rows[-limit:]
    return rows


def harvest_for(session_dir: str, name: str,
                limit: Optional[int] = None) -> List[Dict]:
    """Harvest by (session_dir, process name); [] when no ring exists."""
    return harvest(ring_path(session_dir, name), limit)


class _Timed(NamedTuple):
    """A :data:`Mark` and, off its detail's end, :func:`mark_since`'s cost
    (``""`` where the record has none)."""
    process: str
    kind: str
    start: float
    end: float
    detail: str
    cost: str


def _timed_records(session_dir: str, kinds: Tuple[str, ...]
                   ) -> Iterator[_Timed]:
    """Every record of every ring of ``session_dir`` whose kind starts with
    one of ``kinds``: a leading duration is taken off the detail and back
    from the stamp (a record without one is a point), and the cost off its
    end."""
    for path in glob.glob(ring_path(session_dir, "*")):
        name = os.path.basename(path)[:-len(".ring")]
        for r in harvest(path):
            if not r["kind"].startswith(kinds):
                continue
            head, _, rest = r["detail"].partition("|")
            try:
                secs, detail = float(head), rest
            except ValueError:
                secs, detail = 0.0, r["detail"]
            cost = ""
            if detail.rpartition("|")[2].startswith("cpu="):
                detail, _, cost = detail.rpartition("|")
            yield _Timed(name, r["kind"], r["ts"] - secs, r["ts"], detail,
                         cost)


def _values(part: str) -> Dict[str, float]:
    """``name=<number> ...`` as a table."""
    found: Dict[str, float] = {}
    for token in part.split():
        name, _, value = token.rpartition("=")
        try:
            found[name] = float(value)
        except ValueError:
            continue    # a record cut at MAX_PAYLOAD ends in half a token
    return found


def bringup_gap(marks: List[Mark]) -> Optional[float]:
    """Seconds between the start of the first ``bringup.*`` mark and the last
    ``train_fn_enter`` that no ``bringup.*`` mark of any process covers;
    ``None`` where no train function was entered."""
    spans = sorted((m[2], m[3]) for m in marks if m[1].startswith("bringup."))
    entered = [m[3] for m in marks if m[1] == ENTERED]
    if not entered:
        return None
    end = max(entered)
    gap, reached = 0.0, spans[0][0]
    for a, b in spans:
        if a >= end:
            break
        if a > reached:
            gap += a - reached
        reached = max(reached, min(b, end))
    return gap + max(0.0, end - reached)


def bringup_timeline(session_dir: str
                     ) -> Tuple[List[Mark], Optional[float]]:
    """A job's start as the processes of ``session_dir`` wrote it: every
    ``bringup.*`` mark and ``compile`` / ``compile.cache`` record of every
    ring, ordered by start on the one clock, and :func:`bringup_gap` of
    them.  A record without a leading duration (``compile.cache|hit``) is a
    point."""
    marks: List[Mark] = sorted(
        (tuple(r[:5]) for r in _timed_records(session_dir,
                                               ("bringup.", "compile"))),
        key=lambda m: (m[2], m[3]))
    return marks, bringup_gap(marks)


def _innermost(spans: List[Tuple[float, float, str]], lo: float, hi: float,
               named: Dict[str, float]) -> float:
    """``[lo, hi]`` by the span that covers each moment innermost — of those
    that cover it the one that began last, and of two that began together
    the shorter — added into ``named`` by name; returns the seconds that no
    span covers."""
    spans = [(max(a, lo), min(b, hi), name) for a, b, name in spans
             if min(b, hi) > max(a, lo)]
    edges = sorted({lo, hi} | {t for a, b, _ in spans for t in (a, b)})
    bare = 0.0
    for a, b in zip(edges, edges[1:]):
        over = [s for s in spans if s[0] <= a and s[1] >= b]
        if not over:
            bare += b - a
            continue
        name = max(over, key=lambda s: (s[0], -s[1]))[2]
        named[name] = named.get(name, 0.0) + b - a
    return bare


def start_account(session_dir: str, since: float = 0.0
                  ) -> Optional[Dict[str, Any]]:
    """A start, accounted: the interval from the beginning of the first
    ``bringup.*`` mark of ``session_dir`` that ends after ``since`` to the
    last ``bringup.first_report``, by what it went to.  ``None`` until a
    train worker has reported.

    ``named``: seconds by name, each moment given to the mark or ``compile``
    record that covers it innermost (so a mark's entry is what none of its
    children holds, and a jitted function traced inside another's trace is
    counted once).  A mark goes by its kind, a ``compile`` record by
    ``compile|<stage>``.  Up to the last ``train_fn_enter`` every process's
    marks count, a worker's only where it is of the gang; after it, the
    gang's workers' alone.  ``unnamed``: the seconds no mark or record
    covers; with ``named`` it sums to ``total``.  ``unnamed_by``: those
    seconds ``before_loop`` (the runtime's and the trainer's) and
    ``in_loop`` (the user's function: what it does between the program's
    marks, its imports among it).  ``marks``: of every kind
    written through :func:`mark_since`, the longest mark whole — ``seconds``,
    ``cpu``, ``off_cpu`` (the seconds its thread did not compute in),
    ``majflt``, ``inblock``.  ``points``: the details of the records without
    a duration (``compile.cache_dir``, ``bringup.worker.chip_on_arrival``),
    in order, by kind."""
    rows = sorted((r for r in _timed_records(session_dir,
                                             ("bringup.", "compile"))
                   if r.end >= since), key=lambda r: (r.start, r.end))
    reported = [r.end for r in rows if r.kind == FIRST_REPORT]
    entered = [r for r in rows if r.kind == ENTERED]
    if not reported or not entered:
        return None
    gang = {r.process for r in entered}
    workers = {r.process for r in rows
               if r.kind.startswith("bringup.worker.")}
    begin = min(r.start for r in rows if r.kind.startswith("bringup."))
    enter, end = max(r.end for r in entered), max(reported)
    before: List[Tuple[float, float, str]] = []
    after: List[Tuple[float, float, str]] = []
    marks: Dict[str, Dict[str, float]] = {}
    points: Dict[str, List[str]] = {}
    for process, kind, a, b, detail, cost in rows:
        if process in workers - gang or (
                kind == "bringup.worker_spawn" and detail not in gang):
            continue    # a pooled worker that is not of this gang
        if b <= a:
            if kind not in (ENTERED, FIRST_REPORT):
                points.setdefault(kind, []).append(detail)
            continue
        name = kind
        if kind == "compile":
            name = f"compile|{detail.partition('|')[0]}"
        elif not kind.startswith("bringup."):
            continue
        before.append((a, b, name))
        if process in gang:
            after.append((a, b, name))
        if cost and b - a >= marks.get(kind, {}).get("seconds", 0.0):
            used = _values(cost)
            marks[kind] = {"seconds": b - a, **used,
                           "off_cpu": max(0.0, b - a - used.get("cpu", 0.0))}
    named: Dict[str, float] = {}
    by = {"before_loop": _innermost(before, begin, enter, named),
          "in_loop": _innermost(after, enter, end, named)}
    return {"total": end - begin, "unnamed": sum(by.values()),
            "unnamed_by": by, "named": named, "marks": marks,
            "points": points}


# --- the steady state: spans without a profiler session, rounds ------------
ROUNDS = "train.rounds"                 # a train worker's report rounds
STALL = "train.stall"                   # one stalled round of them, alone
DRIVER_ROUNDS = "train.driver_rounds"   # the trainer's side of the rounds
_ROUNDS_SUM_S = 0.5     # rounds are summed into one record until this passed
_STALL_MIN_S = 0.25     # a shorter round is never a stalled one
_JUDGED_OVER, _JUDGED_AFTER = 64, 8     # rounds the median is of / needs

_spans = threading.local()


def add_span(name: str, seconds: float) -> None:
    """``seconds`` and one count onto ``name`` in this thread's table."""
    try:
        table = _spans.table
    except AttributeError:
        table = _spans.table = {}
    try:
        entry = table[name]
        entry[0] += seconds
        entry[1] += 1
    except KeyError:
        table[name] = [seconds, 1]


def take_spans() -> Dict[str, List[float]]:
    """This thread's table, ``name -> [seconds, count]`` since the last take,
    and an empty one in its place."""
    table, _spans.table = getattr(_spans, "table", {}), {}
    return table


def round_detail(counts: Dict[str, float], seconds: Dict[str, float]) -> str:
    """``k=v ...;name=seconds ...`` in what ``MAX_PAYLOAD`` leaves after the
    kind and the mark's duration: the largest seconds first, and what does
    not fit left out."""
    tokens = [" ".join(f"{k}={v}" if isinstance(v, int) else f"{k}={v:.6f}"
                       for k, v in counts.items()) + ";"]
    room = MAX_PAYLOAD - 48 - len(tokens[0].encode())
    for name, secs in sorted(seconds.items(), key=lambda kv: -kv[1]):
        token = f"{name}={secs:.6f}"
        room -= len(token.encode()) + 1
        if room < 0:
            break
        tokens.append(token)
    return tokens[0] + " ".join(tokens[1:])


def _parse_round(detail: str) -> Tuple[Dict[str, float], Dict[str, float]]:
    counts, _, seconds = detail.partition(";")
    return _values(counts), _values(seconds)


class RoundLog:
    """The rounds of one loop as timed marks of ``kind``.

    The ring is a budget (a worker's also holds its start, which is read
    after the run): consecutive rounds are summed into one record until
    ``_ROUNDS_SUM_S`` have passed, so a loop whose round is 59 ms writes two
    records a second and not seventeen.  A record's detail is
    ``rounds=<n> [steps=<n> ...] longest=<s>;<name>=<s> ...``: what it
    covers, its longest round, and the seconds by where they went.

    Rounds are judged by the step, since a loop may report every step while
    it warms up and every eighth after: with ``m`` the median of seconds a
    step over the last 64 rounds (none is judged before 8 are in), a round of
    ``n`` steps is *stalled* when it ran at least ``_STALL_MIN_S`` and at
    least half of ``n * m`` over ``n * m``.  A stalled round is written at
    once, as a record of its own.  So is a round without a step in a loop
    that makes steps (its last report, an evaluation): there is no ``m`` to
    hold it to, and it is not judged.  A loop that never counts a step is
    judged as one step a round.
    """

    def __init__(self, kind: str):
        self.kind = kind
        self._per_step = collections.deque(maxlen=_JUDGED_OVER)
        self._stepped = False   # a round of this loop has had a step
        self._counts: Dict[str, float] = {}
        self._seconds: Dict[str, float] = {}
        self._summed = 0.0
        self._ended = 0.0       # time.time() at the last summed round's end

    def close(self, seconds: float, by: Dict[str, float], steps: int = 0,
              **counts: int) -> Optional[float]:
        """One round: ``seconds`` long, ``by`` saying where they went,
        ``steps`` training steps in it and ``counts`` of what happened in
        it.  Returns ``n * m`` where the round is stalled, else ``None``."""
        self._stepped = self._stepped or steps > 0
        judged = steps > 0 or not self._stepped
        n = max(1, steps)
        expected = None
        if judged:
            if seconds >= _STALL_MIN_S \
                    and len(self._per_step) >= _JUDGED_AFTER:
                usual = n * statistics.median(self._per_step)
                if seconds - usual >= 0.5 * usual:
                    expected = usual
            self._per_step.append(seconds / n)
        alone = expected is not None or not judged
        if alone:
            self.flush()
        mine = self._counts
        mine["rounds"] = mine.get("rounds", 0) + 1
        if steps:
            counts["steps"] = steps
        for name, value in counts.items():
            mine[name] = mine.get(name, 0) + value
        mine["longest"] = max(mine.get("longest", 0.0), float(seconds))
        for name, value in by.items():
            self._seconds[name] = self._seconds.get(name, 0.0) + value
        self._summed += seconds
        self._ended = time.time()
        if alone or self._summed >= _ROUNDS_SUM_S:
            self.flush()
        return expected

    def flush(self) -> None:
        """Write what is summed, if anything, as one mark that ends where its
        last round did."""
        if not self._counts:
            return
        mark(self.kind, self._summed,
             round_detail(self._counts, self._seconds), self._ended)
        self._counts, self._seconds, self._summed = {}, {}, 0.0


class Round(NamedTuple):
    """One ``train.rounds`` / ``train.stall`` / ``train.driver_rounds``
    record read back: ``counts`` is the detail before the ``;``, ``seconds``
    the part after it; a stall's ``driver`` is the driver's record that
    overlaps it longest."""
    process: str
    kind: str
    start: float
    end: float
    counts: Dict[str, float]
    seconds: Dict[str, float]
    driver: Optional["Round"] = None

    def each(self) -> List[float]:
        """The seconds of each round the record sums, as far as it says: the
        longest, and the others at their mean."""
        n, longest = int(self.counts.get("rounds", 1)), self.counts.get(
            "longest", self.end - self.start)
        if n <= 1:
            return [self.end - self.start]
        return [longest] + [(self.end - self.start - longest) / (n - 1)] * (
            n - 1)


def round_timeline(session_dir: str) -> List[Round]:
    """A job's steady state as the processes of ``session_dir`` wrote it:
    the train workers' round records and stalls and the driver's round
    records, ordered by start on the one clock — :func:`bringup_timeline`'s
    sibling for what comes after ``train_fn_enter``."""
    rounds = sorted(
        (Round(name, kind, start, end, *_parse_round(detail))
         for name, kind, start, end, detail, _ in _timed_records(
             session_dir, (ROUNDS, STALL, DRIVER_ROUNDS))),
        key=lambda r: (r.start, r.end))
    drivers = [r for r in rounds if r.kind == DRIVER_ROUNDS]

    def beside(stall: Round) -> Optional[Round]:
        overlap = lambda d: min(stall.end, d.end) - max(stall.start, d.start)  # noqa: E731
        best = max(drivers, key=overlap, default=None)
        return best if best is not None and overlap(best) > 0 else None

    return [r._replace(driver=beside(r)) if r.kind == STALL else r
            for r in rounds]
