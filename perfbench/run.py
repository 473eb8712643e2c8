#!/usr/bin/env python3
"""Run one benchmark cell on the chips of this machine.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s>
        --trace <0|1>

The cell, its configuration, traffic mix and metrics are found by name
from ``BENCHMARK.json``. Needs TPUs: with no accelerator, or fewer chips
than the cell asks for, it prints no result and exits 2. The last line
of standard output is the result, one JSON object; the last lines of
standard error are the numbers compared with their limits.
"""

import time

T0 = time.perf_counter()   # set-up is timed from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: JAX's persistent compilation cache, at a fixed path in the checkout
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from perfbench import harness, registry

    cell = registry.load_cell(ROOT, args.workload)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        harness.say(f"needs a TPU; JAX found {devices[0].platform!r}")
        return 2
    if len(devices) < cell.chips:
        harness.say(f"{cell.name} needs {cell.chips} chips; JAX found "
                    f"{len(devices)}")
        return 2
    # before the first compile; the program, too, finds its cache
    # through this variable
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    harness.enable_cache(CACHE_DIR)
    result = harness.run_cell(ROOT, cell, args.seed, args.seconds,
                              bool(args.trace), devices[:cell.chips], T0)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
