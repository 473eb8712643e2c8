#!/usr/bin/env python3
"""The control of a cell, on the chip at the cell's own size.

    python3 perfbench/control.py --workload <name> --seeds 1,2,3

For each seed: the cell's inputs, then the configuration's plain
reference with one stated guarantee broken (``Reference.control_output``)
put in the program's place, and the same comparison a run makes. Every
seed must read above a limit: that is the upper reading each limit is
set under. The benchmark's own runs never run this.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from perfbench import harness, registry
    from perfbench.gen import make_inputs

    cell = registry.load_cell(ROOT, args.workload)
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        harness.say(f"needs {cell.chips} TPU chip(s)")
        return 2
    harness.enable_cache(os.path.join(ROOT, ".jax_cache"))
    mesh = Mesh(devices[:cell.chips], ("x",))
    mod = registry.reference(ROOT, cell.config["reference"])
    failed_all = True
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        xs = make_inputs(mesh, "x", cell.config, cell.traffic, seed,
                         int(cell.config["records_per_chip"]))
        ref = mod.Reference(cell.config, devices[0], cell.chips)
        out, totals = ref.control_output(xs[0], 0,
                                         NamedSharding(mesh, P("x")))
        got = ref.numbers(xs[0], 0, out, totals)
        fails = {k: got[k] > mod.LIMITS[k] for k in mod.LIMITS}
        failed_all &= any(fails.values())
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "control": got, "limits": mod.LIMITS,
                          "fails": fails,
                          "seconds": time.perf_counter() - t0}), flush=True)
        del xs, out, totals, ref
    return 0 if failed_all else 1


if __name__ == "__main__":
    sys.exit(main())
