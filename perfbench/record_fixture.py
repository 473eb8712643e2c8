#!/usr/bin/env python3
"""Record a small profiler trace of one cell, as a test fixture.

    python3 perfbench/record_fixture.py --workload <cell> --seed <n>
        --jobs <n> --out perfbench/fixtures/<cell>.scoped.xplane.pb

One run of the cell through the harness with the profiler on, tracing
``--jobs`` warm jobs (the traffic's ``traced_jobs`` replaced), on the
chips of this machine; the trace file is copied to ``--out`` and the
run's result line printed. Needs TPUs, as ``run.py`` does.
"""

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--jobs", type=int, default=3)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from perfbench import harness, registry, run
    from perfbench.trace_reduce import find_xplane

    cell = registry.load_cell(ROOT, args.workload)
    cell = dataclasses.replace(
        cell, traffic=dict(cell.traffic, traced_jobs=args.jobs))
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        harness.say(f"{cell.name} needs {cell.chips} TPU chip(s)")
        return 2
    os.environ["JAX_COMPILATION_CACHE_DIR"] = run.CACHE_DIR
    harness.enable_cache(run.CACHE_DIR)
    # beside the benchmark's own traces, where the metric readers look
    trace_dir = os.path.join(ROOT, registry.BENCH_DIR, "out", "trace",
                             cell.name + ".fixture")
    result = harness.run_cell(ROOT, cell, args.seed, 0.0, True,
                              devices[:cell.chips], T0, trace_dir=trace_dir)
    shutil.copyfile(find_xplane(trace_dir), args.out)
    harness.say(f"fixture {args.out}: {os.path.getsize(args.out)} bytes")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
