"""One shuffle job through the user entry point, in the order a Spark job
uses it::

    [sample ->] register_shuffle -> get_writer(h).write(x).stop(True)
    -> get_reader(h, key_ordering=...).read() -> output ready
    -> unregister_shuffle

What the job does is read from the configuration: the partitioner
(``range`` from a sample of the keys, as ``sortByKey`` builds it, or
``hash``), the number of partitions and whether the reader orders keys.
Each phase is a host span (``job:*``), written into the profiler's trace
when one is recording.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Optional

import numpy as np


@dataclasses.dataclass
class JobRecord:
    start: float          # host clock, s
    end: float            # host clock, s: output ready and unregistered
    plan_s: float         # sampling + writer.stop (the plan and size exchange)
    counts: np.ndarray    # the plan's [chips, parts] table, for readers
    out: Optional[object] = None
    totals: Optional[object] = None

    @property
    def seconds(self) -> float:
        return self.end - self.start


def num_parts(config: dict, chips: int) -> int:
    return int(config.get("num_parts") or config["parts_per_chip"] * chips)


class ShuffleJob:
    def __init__(self, manager, config: dict):
        from sparkrdma_tpu.exchange.partitioners import hash_partitioner
        from sparkrdma_tpu.meta.sampling import make_sampler

        self.m = manager
        rt = manager.runtime
        self.kw = int(config["key_words"])
        self.parts = num_parts(config, rt.num_partitions)
        self.key_ordering = bool(config["key_ordering"])
        self.sampler = None
        self.partitioner = None
        kind = config["partitioner"]
        if kind == "range":
            # built once, as a long-running application would; every job
            # still samples its own input
            self.sampler = make_sampler(rt.mesh, rt.axis_name, self.kw,
                                        int(config["samples_per_chip"]))
        elif kind == "hash":
            self.partitioner = hash_partitioner(self.parts, self.kw)
        else:
            raise ValueError(f"unknown partitioner {kind!r}")

    def run(self, shuffle_id: int, records) -> JobRecord:
        import jax
        from jax.profiler import TraceAnnotation as span
        from sparkrdma_tpu.exchange.partitioners import range_partitioner
        from sparkrdma_tpu.meta.sampling import compute_splitters

        m = self.m
        with span("job"):
            t0 = time.perf_counter()
            part = self.partitioner
            if self.sampler is not None:
                with span("job:sample"):
                    samples = np.asarray(jax.device_get(
                        self.sampler(records)))
                    part = range_partitioner(
                        compute_splitters(samples, self.parts), self.kw)
            h = m.register_shuffle(shuffle_id, self.parts, part)
            with span("job:plan"):
                plan = m.get_writer(h).write(records).stop(True)
            t1 = time.perf_counter()
            with span("job:read"):
                out, totals = m.get_reader(
                    h, key_ordering=self.key_ordering).read()
            with span("job:wait"):
                jax.block_until_ready((out, totals))
            with span("job:unregister"):
                m.unregister_shuffle(shuffle_id)
            t2 = time.perf_counter()
        return JobRecord(start=t0, end=t2, plan_s=t1 - t0,
                         counts=plan.counts, out=out, totals=totals)
