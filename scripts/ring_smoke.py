"""Compiled-mode smoke of the pallas_ring kernel on real TPU hardware.

The CPU test mesh can only run the ring kernel in interpret mode (the
HLO interpreter has no lowering for collective semaphores), so this
script provides the compiled-coverage leg: on however many real chips
are attached it runs the ring transport COMPILED (collective=True on
>1 chip — real barrier handshake + remote DMA; on 1 chip the kernel
still compiles and executes its local-copy path through Mosaic), checks
the result against lax.all_to_all, and times both transports.

Run: python scripts/ring_smoke.py   (TPU env)
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from sparkrdma_tpu.utils.stats import barrier


def main() -> int:
    devs = jax.devices()
    n = len(devs)
    print(f"platform={devs[0].platform} devices={n}", flush=True)
    mesh = Mesh(np.array(devs), ("shuffle",))

    # force the compiled (non-interpret) path regardless of chip count:
    # num_devices=1 short-circuits inside make_ring_all_to_all, so build
    # the kernel call directly
    from functools import partial

    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from sparkrdma_tpu.exchange.ring import _a2a_kernel

    per = 1 << 20
    w = 4

    def ring_a2a(slots):
        kernel = partial(_a2a_kernel, axis_name="shuffle",
                         num_devices=n, collective=(n > 1))
        return pl.pallas_call(
            kernel,
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            out_shape=jax.ShapeDtypeStruct(slots.shape, slots.dtype,
                                           vma=frozenset({"shuffle"})),
            scratch_shapes=[
                pltpu.SemaphoreType.DMA((n,)),
                pltpu.SemaphoreType.DMA((n,)),
                pltpu.SemaphoreType.DMA,
            ],
            compiler_params=pltpu.CompilerParams(
                has_side_effects=True,
                # collective_id is only legal with the barrier-semaphore
                # handshake, which needs >1 device
                collective_id=(7 if n > 1 else None)),
            interpret=False,
        )(slots)

    def xla_a2a(slots):
        if n == 1:
            return slots
        return lax.all_to_all(slots, "shuffle", split_axis=0,
                              concat_axis=0, tiled=True)

    rng = np.random.default_rng(0)
    # [P, W, per]: keep the long axis minor (a 4-wide minor dim has no
    # Mosaic layout; the real exchange's slots are [mesh, ppd, W, C] for
    # the same reason)
    x = rng.integers(0, 2**32, size=(n * n, w, per), dtype=np.uint32)
    xg = jax.device_put(jnp.asarray(x), NamedSharding(mesh, P("shuffle")))

    fns = {}
    for name, a2a in (("ring", ring_a2a), ("xla", xla_a2a)):
        fns[name] = jax.jit(shard_map(
            a2a, mesh=mesh, in_specs=(P("shuffle"),),
            out_specs=P("shuffle"), check_vma=False))

    outs = {}
    for name, fn in fns.items():
        out = fn(xg)
        barrier(out)
        t0 = time.perf_counter()
        for _ in range(4):
            out = fn(xg)
        barrier(out)
        dt = (time.perf_counter() - t0) / 4
        outs[name] = np.asarray(out)
        gb = x.nbytes / 1e9
        print(f"{name:5s} a2a: {dt*1e3:8.2f} ms  ({gb/dt:6.2f} GB/s)",
              flush=True)
    ok = np.array_equal(outs["ring"], outs["xla"])
    print(f"ring == xla: {ok}", flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
