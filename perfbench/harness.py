"""One run of one cell: set-up, the measured window, the check.

1. Set-up (``setup_s``, from process start): the runtime and the
   manager, the cell's inputs made on the device from the seed, and
   ``warmup_jobs_per_input`` whole jobs on each input, which compile or
   load every program the window uses (the output copy of a checked job
   too).
2. The window: closed loop, concurrency 1, fresh shuffle id per job,
   inputs alternating (``window.py``). With ``trace`` the profiler
   records ``traced_jobs`` warm jobs and the window is those jobs.
3. After the window: peak device memory is read, the program's state is
   freed, and the plain reference checks the jobs drawn for checking:
   one job among the window's first four, drawn from the seed (its
   output is copied on the device when it ends, since the next job
   reuses the buffer), and the window's last job.
"""

from __future__ import annotations

import json
import math
import os
import shutil
import statistics
import sys
import time
from types import SimpleNamespace
from typing import List, Optional

import numpy as np

from perfbench import registry
from perfbench.clock import CompileClock, peak_bytes
from perfbench.gen import make_inputs, seed_words
from perfbench.job import ShuffleJob
from perfbench.stall import StallWatch
from perfbench.window import Window, closed_loop

FIRST_SHUFFLE_ID = 1000
#: a job longer than this many times the median job is a stall
STALL_FACTOR = 1.5
#: the checked job is drawn from the window's first CHECK_SPAN jobs
CHECK_SPAN = 4


def say(msg: str) -> None:
    print(f"perfbench: {msg}", file=sys.stderr, flush=True)


def enable_cache(path: str) -> None:
    """JAX's persistent compilation cache at a fixed path, every program
    in it (so only a checkout's first run compiles)."""
    import jax

    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def _trace_options():
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0   # host spans yes, Python calls no
    return opts


def run_cell(root: str, cell: registry.Cell, seed: int, seconds: float,
             trace: bool, devices, t0: float,
             records_per_chip: Optional[int] = None,
             trace_dir: Optional[str] = None) -> dict:
    """Runs the cell; returns the result line as a dict (``checks``
    last). ``records_per_chip`` overrides the configuration's size (CPU
    tests only)."""
    import jax
    import jax.numpy as jnp
    from sparkrdma_tpu import MeshRuntime, ShuffleConf
    from sparkrdma_tpu.api.shuffle_manager import ShuffleManager

    cfg, tr = cell.config, cell.traffic
    n_chip = int(records_per_chip or cfg["records_per_chip"])
    conf = ShuffleConf(key_words=int(cfg["key_words"]),
                       val_words=int(cfg["val_words"]),
                       **cfg["shuffle_conf"])
    chips = len(devices)
    record_bytes = 4 * conf.record_words
    clock = CompileClock()
    watch = StallWatch()
    try:
        manager = ShuffleManager(MeshRuntime(conf, devices=devices), conf)
        inputs = make_inputs(manager.runtime.mesh, manager.runtime.axis_name,
                             cfg, tr, seed, n_chip)
        job = ShuffleJob(manager, cfg)
        sids = iter(range(FIRST_SHUFFLE_ID, 1 << 30))
        for x in inputs:
            for _ in range(int(tr["warmup_jobs_per_input"])):
                rec = job.run(next(sids), x)
                jax.block_until_ready(jnp.copy(rec.out))
        del rec
        setup_s = time.perf_counter() - t0
        setup_compile_s, setup_programs = clock.seconds, clock.programs
        checked = int(np.random.default_rng(seed_words(seed, 4)).integers(
            CHECK_SPAN))
        kept = {}
        records: List = []

        def run_job(i: int):
            if records:
                records[-1].out = None   # its buffer is the next job's
            x_id = i % len(inputs)
            durations = [r.seconds for r in records]
            # a job that fails raises: the run ends with no result
            watch.begin(i, STALL_FACTOR * statistics.median(durations)
                        if len(durations) >= 3 else None)
            rec = job.run(next(sids), inputs[x_id])
            watch.end()
            rec.input_id = x_id
            if i == checked:
                kept[checked] = jnp.copy(rec.out)
            records.append(rec)
            return rec

        if trace:
            trace_dir = trace_dir or os.path.join(
                root, registry.BENCH_DIR, "out", "trace", cell.name)
            shutil.rmtree(trace_dir, ignore_errors=True)
            jax.profiler.start_trace(trace_dir,
                                     profiler_options=_trace_options())
            try:
                closed_loop(run_job, math.inf,
                            max_jobs=int(tr["traced_jobs"]))
            finally:
                jax.profiler.stop_trace()
        else:
            closed_loop(run_job, seconds)
        window_programs = clock.programs - setup_programs
        memory_peak = peak_bytes(devices)
        sort_mode = manager._exchange.sort_mode(conf.record_words)
        last = len(records) - 1
        kept[last] = records[last].out
        checks = {i: (records[i].input_id, kept[i], records[i].totals)
                  for i in sorted(kept)}
        for r in records:
            r.out = r.totals = None
        manager.stop()
        del manager, job
    finally:
        clock.close()
        watch.close()

    window = Window([r.start for r in records], [r.end for r in records],
                    n_chip * chips * record_bytes, chips)
    summary = None
    if trace:
        from perfbench.trace_reduce import reduce_dir

        summary = reduce_dir(trace_dir)
    run = SimpleNamespace(chips=chips, record_bytes=record_bytes,
                          setup_s=setup_s, window=window, jobs=records,
                          traced_jobs=records if trace else [],
                          trace=summary)
    wanted = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m in wanted:
        value = registry.metric_reader(root, m["name"]).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    slow = STALL_FACTOR * statistics.median(window.durations)
    say("notes " + json.dumps({
        "workload": cell.name, "seed": seed, "chips": chips,
        "records_per_chip": n_chip, "record_bytes": record_bytes,
        "jobs": len(records),
        "window_s": window.seconds,
        "job_s_min_median_max": [min(window.durations),
                                 statistics.median(window.durations),
                                 max(window.durations)],
        # jobs over STALL_FACTOR x the median, a stall in the window:
        # [index, seconds, of them sampling + plan, of them in the
        # garbage collector, where the host was as the job ran long]
        "slow_jobs": [[i, r.seconds, r.plan_s, watch.gc_s.get(i, 0.0),
                       watch.samples.get(i, [])]
                      for i, r in enumerate(records) if r.seconds > slow],
        "gc_s_in_window": sum(watch.gc_s.values()),
        "setup_compile_s": setup_compile_s,
        "setup_programs": setup_programs,
        "window_programs": window_programs,
        "sort_mode": sort_mode,
        "memory_peak_bytes_per_chip": [
            int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
            for d in devices],
        "checked_jobs": sorted(checks)}))
    if window_programs:
        say(f"WARNING: {window_programs} program(s) compiled or loaded "
            "inside the window")

    ref_mod = registry.reference(root, cfg["reference"])
    ref = ref_mod.Reference(cfg, devices[0], chips)
    limits = ref_mod.LIMITS
    worst = {k: 0 for k in limits}
    for i, (x_id, out, totals) in checks.items():
        got = ref.numbers(inputs[x_id], x_id, out, totals)
        say(f"check job {i} (input {x_id}): {json.dumps(got)}")
        worst = {k: max(worst[k], int(got[k])) for k in limits}
    correct = all(worst[k] <= limits[k] for k in limits)

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": chips,
              "memory_peak_bytes": memory_peak}
    result = {"correct": correct, "attempted": len(records),
              "failed": 0, "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        result["breakdown"] = {
            "device_ops": [[k, v] for k, v in summary.top_ops],
            "idle_gaps": [[k, v] for k, v in summary.idle_gaps]}
        say("trace " + json.dumps({"jobs": summary.jobs,
                                   "class_s": summary.class_s}))
    result["checks"] = {k: {"value": worst[k], "limit": limits[k]}
                        for k in limits}
    for k in limits:
        say(f"check {k} {worst[k]} limit {limits[k]}")
    return result
