"""From a profiler trace (``.xplane.pb``) to device busy and idle time,
device time by op class, the top device ops and labelled idle gaps.

Read with ``jax.profiler.ProfileData`` and nothing else. Device ops are
the events on each TPU plane's ``XLA Ops`` line. The traced window is
taken from the trace itself: from the first ``job`` host span's start to
the last one's end (the benchmark writes one such span per job).

- busy: the union of a chip's op intervals inside the window;
- op classes, by HLO opcode until the program names its phases:
  ``sort`` (``sort`` ops: the reduce-side sort and the map-side bucket
  sort together), ``a2a`` (``all-to-all`` ops and the ring transport's
  Pallas kernels), ``other``;
- idle gaps: the stretches of the window in which chip 0 runs no op,
  each labelled by the innermost host span open at its middle (the
  benchmark's ``job:*`` spans and the program's ``shuffle:*``
  annotations), ``host:between_jobs`` where none is.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

JOB_SPAN = "job"
HOST_SPAN_PREFIXES = ("job", "shuffle:")
OPS_LINE = "XLA Ops"
RING_KERNELS = ("_ring_exchange_kernel", "_a2a_kernel")

Interval = Tuple[int, int]


@dataclasses.dataclass
class Op:
    name: str
    start: int   # ns
    end: int     # ns
    cls: str


@dataclasses.dataclass
class Summary:
    chips: int
    jobs: int
    window_s: float
    busy_s: float                   # mean over chips
    class_s: Dict[str, float]       # mean over chips
    top_ops: List[Tuple[str, float]]    # per chip, longest first
    idle_gaps: List[Tuple[str, float]]  # chip 0, longest first

    @property
    def idle_pct(self) -> float:
        return 100.0 * (1.0 - self.busy_s / self.window_s)


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def parse_hlo(text: str) -> Tuple[str, str, str]:
    """``(name, opcode, shape)`` of an op event, whose name on a TPU is
    the instruction's HLO text, ``%name = <shape> <opcode>(...)``; the
    shape loses its layouts. A bare name parses as its own opcode."""
    name, sep, rest = text.partition(" = ")
    if not sep:
        return text, text.split(".")[0].lstrip("%"), ""
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += {"(": 1, ")": -1}.get(ch, 0)
            if depth == 0:
                break
        shape, rest = rest[:i + 1], rest[i + 1:].lstrip()
    else:
        shape, _, rest = rest.partition(" ")
    return name, rest.split("(")[0], re.sub(r"\{[^{}]*\}", "", shape)


def classify(text: str) -> str:
    _, opcode, _ = parse_hlo(text)
    if opcode.startswith("all-to-all") or any(k in text
                                              for k in RING_KERNELS):
        return "a2a"
    if opcode == "sort":
        return "sort"
    return "other"


def short_name(text: str) -> str:
    name, opcode, shape = parse_hlo(text)
    return f"{opcode} {name} {shape}".strip()[:120]


def union(intervals: Sequence[Interval]) -> List[Interval]:
    merged: List[List[int]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def gaps(busy: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


def host_spans(pd) -> List[Tuple[str, int, int]]:
    spans = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(HOST_SPAN_PREFIXES):
                    s = int(ev.start_ns)
                    spans.append((ev.name, s, s + int(ev.duration_ns)))
    return spans


def device_ops(pd) -> List[List[Op]]:
    """Per TPU chip, its ops in start order."""
    chips = []
    for plane in pd.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            ops = []
            for ev in line.events:
                s = int(ev.start_ns)
                ops.append(Op(ev.name, s, s + int(ev.duration_ns),
                              classify(ev.name)))
            chips.append(sorted(ops, key=lambda o: o.start))
    return chips


def label(spans: Sequence[Tuple[str, int, int]], t: int) -> str:
    """The innermost (latest-starting) host span open at ``t``."""
    best: Optional[Tuple[str, int, int]] = None
    for sp in spans:
        if sp[1] <= t < sp[2] and (best is None or sp[1] >= best[1]):
            best = sp
    return best[0] if best is not None else "host:between_jobs"


def reduce(pd, top: int = 10) -> Summary:
    spans = host_spans(pd)
    jobs = [sp for sp in spans if sp[0] == JOB_SPAN]
    if not jobs:
        raise ValueError("trace holds no 'job' host span")
    lo, hi = min(sp[1] for sp in jobs), max(sp[2] for sp in jobs)
    chips = device_ops(pd)
    if not chips:
        raise ValueError("trace holds no TPU 'XLA Ops' line")
    busy_total = 0.0
    class_total: Dict[str, float] = defaultdict(float)
    by_name: Dict[str, float] = defaultdict(float)
    chip0_busy: List[Interval] = []
    for i, ops in enumerate(chips):
        clipped = [(max(o.start, lo), min(o.end, hi), o) for o in ops
                   if o.end > lo and o.start < hi]
        busy = union([(s, e) for s, e, _ in clipped])
        busy_total += sum(e - s for s, e in busy)
        for s, e, o in clipped:
            class_total[o.cls] += e - s
            by_name[f"{o.cls}:{short_name(o.name)}"] += e - s
        if i == 0:
            chip0_busy = busy
    n = len(chips)
    idle = [(label(spans, (s + e) // 2), (e - s) / 1e9)
            for s, e in gaps(chip0_busy, lo, hi)]
    idle.sort(key=lambda g: -g[1])
    ops_top = sorted(((k, v / n / 1e9) for k, v in by_name.items()),
                     key=lambda kv: -kv[1])[:top]
    return Summary(
        chips=n, jobs=len(jobs), window_s=(hi - lo) / 1e9,
        busy_s=busy_total / n / 1e9,
        class_s={k: v / n / 1e9 for k, v in class_total.items()},
        top_ops=ops_top, idle_gaps=idle[:top])


def reduce_dir(trace_dir: str) -> Summary:
    import jax

    return reduce(jax.profiler.ProfileData.from_file(find_xplane(trace_dir)))
