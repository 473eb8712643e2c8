"""Shuffle read statistics — compatibility shim over :mod:`sparkrdma_tpu.obs`.

``ExchangeRecord`` / ``ShuffleReadStats`` (the ``RdmaShuffleReaderStats``
analogue) moved to :mod:`sparkrdma_tpu.obs.stats` where they feed the
unified metrics registry; this module re-exports them so every existing
import path keeps working. ``Timer`` and ``barrier`` (timing utilities,
not stats) live here.
"""

from __future__ import annotations

import time

from sparkrdma_tpu.obs.stats import ExchangeRecord, ShuffleReadStats


class Timer:
    def __enter__(self):
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.elapsed = time.perf_counter() - self.t0


def barrier(*arrays) -> None:
    """Execution barrier for timing: wait until every producing
    executable has finished. Use at the edges of timed regions.

    Accepts anything ``block_until_ready`` does: arrays of any rank
    including 0-d, zero-size arrays, and non-array leaves (skipped).
    """
    import jax

    jax.block_until_ready(arrays)


__all__ = ["ExchangeRecord", "ShuffleReadStats", "Timer", "barrier"]
