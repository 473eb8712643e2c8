"""Where the host is while a job runs long.

A stall in the window (a job far over the median) shows in the tail and
the rate; this says where it sat. A daemon thread looks every
``every_s`` at the job in progress and, once it has run past its limit,
takes the main thread's Python stack, up to ``keep`` times a job. Time
the garbage collector spent inside each job is summed from
``gc.callbacks``. Both cost nothing measurable while no job runs long.
"""

from __future__ import annotations

import gc
import os
import sys
import threading
import time
from typing import Dict, List, Optional


def where(frame, depth: int = 8) -> str:
    """The innermost ``depth`` frames, innermost first."""
    parts = []
    while frame is not None and len(parts) < depth:
        code = frame.f_code
        parts.append(f"{os.path.basename(code.co_filename)}:"
                     f"{frame.f_lineno}:{code.co_name}")
        frame = frame.f_back
    return " < ".join(parts)


class StallWatch:
    def __init__(self, every_s: float = 0.05, keep: int = 8):
        self.every_s = every_s
        self.keep = keep
        #: job index -> [[seconds into the job, stack], ...]
        self.samples: Dict[int, List] = {}
        #: job index -> seconds in the garbage collector
        self.gc_s: Dict[int, float] = {}
        self._main = threading.get_ident()
        self._lock = threading.Lock()
        self._job = None            # (index, start, limit) guarded-by: _lock
        self._gc_start: Optional[float] = None
        self._stop = threading.Event()
        gc.callbacks.append(self._on_gc)
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="perfbench-stall-watch")
        self._thread.start()

    def begin(self, index: int, limit_s: Optional[float]) -> None:
        """Job ``index`` starts; look at it once it passes ``limit_s``
        (``None``: never)."""
        with self._lock:
            self._job = (index, time.perf_counter(), limit_s)

    def end(self) -> None:
        with self._lock:
            self._job = None

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._gc_start = time.perf_counter()
            return
        job = self._job
        if job is not None and self._gc_start is not None:
            self.gc_s[job[0]] = (self.gc_s.get(job[0], 0.0)
                                 + time.perf_counter() - self._gc_start)

    def _loop(self) -> None:
        while not self._stop.wait(self.every_s):
            with self._lock:
                job = self._job
            if job is None or job[2] is None:
                continue
            index, start, limit = job
            late = time.perf_counter() - start
            if late < limit:
                continue
            got = self.samples.setdefault(index, [])
            if len(got) < self.keep:
                got.append([late,
                            where(sys._current_frames().get(self._main))])

    def close(self) -> None:
        self._stop.set()
        self._thread.join()
        gc.callbacks.remove(self._on_gc)
