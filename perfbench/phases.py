"""Device time by program phase, read from a profiler trace.

The program names its phases inside its compiled programs with
``jax.named_scope("sr_<phase>")`` (``sr_sample``, ``sr_count``,
``sr_bucket``, ``sr_slots``, ``sr_exchange``, ``sr_compact``,
``sr_sort_keys``, ``sr_sort_gather``, ``sr_combine``). The scope reaches
the trace as part of the ``tf_op`` stat, JAX's name stack, on each
device op's event metadata, e.g.
``jit(local_step)/sr_sort_gather/jit(_take)/gather``. An op belongs to
the innermost ``sr_*`` component of its ``tf_op``; an op with none is
``unscoped``.

``jax.profiler.ProfileData`` does not expose event metadata stats, so
this module reads the XSpace protobuf (``.xplane.pb``) itself, with a
reader of the protobuf wire format that decodes only the fields needed:

- ``XSpace.planes`` (1);
- ``XPlane``: ``name`` (2), ``lines`` (3), ``event_metadata`` (4, a map
  of id to ``XEventMetadata``: ``id`` 1, ``name`` 2, ``stats`` 5) and
  ``stat_metadata`` (5, a map of id to ``XStatMetadata``: ``id`` 1,
  ``name`` 2);
- ``XLine``: ``name`` (2), ``timestamp_ns`` (3), ``events`` (4);
- ``XEvent``: ``metadata_id`` (1), ``offset_ps`` (2), ``duration_ps`` (3);
- ``XStat``: ``metadata_id`` (1), ``str_value`` (5), ``ref_value`` (7).

Times are on ``ProfileData``'s clock in whole ns (``start = line
timestamp + offset_ps // 1000``, ``end = start + duration_ps // 1000``),
so the window, the op intervals and the host spans are the same numbers
``trace_reduce`` reads. Standard library and ``perfbench`` only.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, Iterator, List, Optional, Tuple

from perfbench import trace_reduce as tr

UNSCOPED = "unscoped"
TF_OP = "tf_op"
#: host spans whose idle gaps are the plan's host share
PLAN_SPANS = ("shuffle:plan", "shuffle:splitters")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACE_ROOT = os.path.join(ROOT, "perfbench", "out", "trace")

_SCOPE = re.compile(r"^sr_[a-z_]+$")


@dataclasses.dataclass
class PhaseOp:
    start: int   # ns
    end: int     # ns
    phase: str


@dataclasses.dataclass
class Trace:
    chips: List[List[PhaseOp]]          # per TPU chip, in start order
    spans: List[Tuple[str, int, int]]   # host spans, as trace_reduce's
    lo: int                             # window start, ns
    hi: int                             # window end, ns
    jobs: int

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) / 1e9


# -- protobuf wire format ---------------------------------------------

def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf: bytes, i: int, end: int) -> Iterator[Tuple[int, object]]:
    """``(field number, value)`` of one message in ``buf[i:end]``: an
    int for a varint or fixed field, ``(start, stop)`` for a
    length-delimited one."""
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value = (i, i + n)
            i += n
        elif wire == 1:
            value = int.from_bytes(buf[i:i + 8], "little")
            i += 8
        elif wire == 5:
            value = int.from_bytes(buf[i:i + 4], "little")
            i += 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, value


def _str(buf: bytes, span) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _map_value(buf: bytes, span) -> Tuple[int, int]:
    """The value message's bounds of one map entry (key 1, value 2)."""
    for f, v in _fields(buf, *span):
        if f == 2:
            return v
    return (span[1], span[1])


def _plane(buf: bytes, span, want_tf_op: bool):
    """``(name, lines, metadata)``: each line as ``(name, timestamp_ns,
    [(metadata_id, offset_ps, duration_ps)])``; metadata maps an event
    metadata id to ``(name, tf_op)``."""
    name, lines, ev_meta, stat_names = "", [], [], {}
    for f, v in _fields(buf, *span):
        if f == 2:
            name = _str(buf, v)
        elif f == 3:
            lines.append(v)
        elif f == 4:
            ev_meta.append(_map_value(buf, v))
        elif f == 5 and want_tf_op:
            sid, sname = 0, ""
            for g, w in _fields(buf, *_map_value(buf, v)):
                if g == 1:
                    sid = w
                elif g == 2:
                    sname = _str(buf, w)
            stat_names[sid] = sname
    metadata: Dict[int, Tuple[str, str]] = {}
    for m in ev_meta:
        mid, mname, stats = 0, "", []
        for f, v in _fields(buf, *m):
            if f == 1:
                mid = v
            elif f == 2:
                mname = _str(buf, v)
            elif f == 5 and want_tf_op:
                stats.append(v)
        tf_op = ""
        for s in stats:
            sid, sval = 0, ""
            for f, v in _fields(buf, *s):
                if f == 1:
                    sid = v
                elif f == 5:
                    sval = _str(buf, v)
                elif f == 7:   # an interned string: a stat metadata id
                    sval = stat_names.get(v, "")
            if stat_names.get(sid) == TF_OP:
                tf_op = sval
        metadata[mid] = (mname, tf_op)
    out_lines = []
    for ln in lines:
        lname, ts, events = "", 0, []
        for f, v in _fields(buf, *ln):
            if f == 2:
                lname = _str(buf, v)
            elif f == 3:
                ts = v
            elif f == 4:
                mid = off = dur = 0
                for g, w in _fields(buf, *v):
                    if g == 1:
                        mid = w
                    elif g == 2:
                        off = w
                    elif g == 3:
                        dur = w
                events.append((mid, off, dur))
        out_lines.append((lname, ts, events))
    return name, out_lines, metadata


# -- the trace --------------------------------------------------------

def phase_of(tf_op: str) -> str:
    """The innermost ``sr_*`` component of a name stack, or
    ``unscoped``."""
    for part in reversed(tf_op.split("/")):
        if _SCOPE.match(part):
            return part
    return UNSCOPED


def parse(data: bytes) -> Trace:
    """Per TPU chip, its ``XLA Ops`` in start order with their phase;
    the host spans ``trace_reduce`` reads; the window from the first
    ``job`` span's start to the last one's end."""
    chips: List[List[PhaseOp]] = []
    spans: List[Tuple[str, int, int]] = []
    for f, v in _fields(data, 0, len(data)):
        if f != 1:
            continue
        start = v[0]
        # the plane's name comes first: peek before decoding the rest
        pname = ""
        for g, w in _fields(data, *v):
            if g == 2:
                pname = _str(data, w)
                break
        device = pname.startswith("/device:TPU:")
        if not device and not pname.startswith("/host:"):
            continue
        _, lines, metadata = _plane(data, (start, v[1]), device)
        for lname, ts, events in lines:
            if device and lname != tr.OPS_LINE:
                continue
            out = []
            for mid, off, dur in events:
                s = ts + off // 1000
                mname, tf_op = metadata.get(mid, ("", ""))
                if device:
                    out.append(PhaseOp(s, s + dur // 1000, phase_of(tf_op)))
                elif mname.startswith(tr.HOST_SPAN_PREFIXES):
                    spans.append((mname, s, s + dur // 1000))
            if device:
                chips.append(sorted(out, key=lambda o: o.start))
    jobs = [sp for sp in spans if sp[0] == tr.JOB_SPAN]
    if not jobs:
        raise ValueError("trace holds no 'job' host span")
    if not chips:
        raise ValueError("trace holds no TPU 'XLA Ops' line")
    return Trace(chips=chips, spans=spans,
                 lo=min(sp[1] for sp in jobs), hi=max(sp[2] for sp in jobs),
                 jobs=len(jobs))


def load(path: str) -> Trace:
    with open(path, "rb") as f:
        return parse(f.read())


def newest_trace() -> Optional[str]:
    """The newest trace file under ``perfbench/out/trace``: the harness
    writes a traced run's trace there (the ``run`` it hands a reader
    does not name the file)."""
    found = glob.glob(os.path.join(TRACE_ROOT, "**", "*.xplane.pb"),
                      recursive=True)
    return max(found, key=os.path.getmtime) if found else None


def of_run(run) -> Optional[Trace]:
    """The phases of a traced run, parsed once and kept on ``run``; None
    for an untraced run, or where the newest trace file does not hold
    the window that ``trace_reduce`` read (the same jobs, to the ns)."""
    if getattr(run, "trace", None) is None:
        return None
    if getattr(run, "phases", None) is None:
        path = newest_trace()
        if path is None:
            return None
        t = load(path)
        if t.jobs != run.trace.jobs or t.window_s != run.trace.window_s:
            return None
        run.phases = t
    return run.phases


def _clipped(t: Trace, ops: List[PhaseOp]) -> List[Tuple[int, int, str]]:
    return [(max(o.start, t.lo), min(o.end, t.hi), o.phase) for o in ops
            if o.end > t.lo and o.start < t.hi]


def phase_s(t: Trace) -> Dict[str, float]:
    """Device seconds by phase, clipped to the window as
    ``trace_reduce`` clips op classes, mean over chips."""
    total: Dict[str, float] = {}
    for ops in t.chips:
        for s, e, phase in _clipped(t, ops):
            total[phase] = total.get(phase, 0.0) + (e - s)
    return {k: v / len(t.chips) / 1e9 for k, v in total.items()}


def scope_ms_per_job(run, scope: str) -> Optional[float]:
    """Device ms in ``scope`` per traced job, per chip; None where the
    scope ran no op in the window."""
    t = of_run(run)
    if t is None:
        return None
    s = phase_s(t).get(scope)
    return 1e3 * s / t.jobs if s else None


def host_idle(t: Trace) -> List[Tuple[str, int]]:
    """Chip 0's idle time in the window by what the host was doing:
    each idle gap split at the host spans' boundaries inside it, each
    piece labelled by the innermost span open in it (``trace_reduce``'s
    rule, applied to the piece and not to the whole gap's middle).
    ``(label, ns)`` per piece."""
    busy = tr.union([(s, e) for s, e, _ in _clipped(t, t.chips[0])])
    out = []
    for s, e in tr.gaps(busy, t.lo, t.hi):
        cuts = sorted({s, e} | {b for _, a, z in t.spans for b in (a, z)
                                if s < b < e})
        out += [(tr.label(t.spans, (a + b) // 2), b - a)
                for a, b in zip(cuts, cuts[1:])]
    return out


def plan_idle_ms_per_job(run) -> Optional[float]:
    """Chip 0's idle ms per traced job while the host's innermost span
    is the plan's (``shuffle:plan`` and its children,
    ``shuffle:splitters``); None where the plan left the chip no idle
    time."""
    t = of_run(run)
    if t is None:
        return None
    idle = [ns for label, ns in host_idle(t) if label.startswith(PLAN_SPANS)]
    return sum(idle) / 1e6 / t.jobs if idle else None
