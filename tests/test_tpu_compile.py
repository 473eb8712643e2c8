"""Compiles for a described TPU v5e: what the chip's compiler refuses,
caught without a chip. Nothing here runs; each test compiles.

Kernels that passed every interpret-mode test were refused by the
chip's compiler (the ring's per-destination slice at W % 8 != 0, the
merge sort's scoped VMEM), so these compile the main path's kernels at
its real record widths: the fused ring exchange at W=13/25 on a 4-chip
mesh, ``merge_sort_cols`` at W=8/25 (and, marked slow, at 4M records
with its own tile choice), and the one-chip TeraSort exchange+sort step
at 100-byte records.

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU library, and every
pytest-xdist worker imports every test module. The persistent
compilation cache is off around these compiles (an entry compiled for a
described device cannot be read back without the chip).
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    log_dir = os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", cache_on)
    compilation_cache.reset_cache()
    if log_dir == "disabled":
        os.environ.pop("TPU_LOG_DIR", None)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("w", [13, 25])
def test_fused_ring_exchange_compiles(topo, w):
    """W=13/25 put the record width in the tiled second-minor dim of
    the ``[R, P, ppd, W, C+1]`` slots; the kernel must DMA tile-aligned
    blocks whatever W and C are (C+1: the counts lane)."""
    from sparkrdma_tpu.exchange.ring import make_ring_exchange

    mesh = Mesh(np.asarray(topo.devices, dtype=object), ("x",))
    rounds, ppd, cap = 2, 1, 4096 + 1
    ex = make_ring_exchange(mesh, "x", rounds)
    fn = jax.shard_map(ex, mesh=mesh, in_specs=P("x"), out_specs=P("x"),
                       check_vma=False)
    slots = jax.ShapeDtypeStruct((4 * rounds, 4, ppd, w, cap), jnp.uint32,
                                 sharding=NamedSharding(mesh, P("x")))
    assert "tpu_custom_call" in _compile(fn, slots).as_text()


@pytest.mark.parametrize("w", [8, 25])
def test_merge_sort_compiles(topo, w):
    """W=25 fills 25 of the 32 rows of its 8-row tiles: the kernel's
    window slices must still be tile-aligned. Smallest working tile —
    Mosaic's compile time grows with tile x W (W=25: ~30 s even here)."""
    from sparkrdma_tpu.kernels.merge_sort import merge_sort_cols

    cols = jax.ShapeDtypeStruct((w, 1 << 12), jnp.uint32,
                                sharding=SingleDeviceSharding(
                                    topo.devices[0]))
    compiled = _compile(lambda c: merge_sort_cols(
        c, run=1 << 10, tile=256, interpret=False), cols)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.slow
def test_merge_sort_auto_tile_fits_vmem(topo):
    """The tile ``_pick_tile`` chooses must fit the kernel's real scoped
    VMEM: W=8 at 4M records once asked for more than Mosaic's limit.
    Slow: the full-size tile takes over a minute to compile."""
    from sparkrdma_tpu.kernels.merge_sort import merge_sort_cols

    cols = jax.ShapeDtypeStruct((8, 1 << 22), jnp.uint32,
                                sharding=SingleDeviceSharding(
                                    topo.devices[0]))
    compiled = _compile(lambda c: merge_sort_cols(c, interpret=False), cols)
    assert "tpu_custom_call" in compiled.as_text()


def test_one_chip_terasort_step_compiles(topo):
    """chip_smoke Phase A's program — the degenerate one-chip exchange
    plus the wide fused sort at W=25 — at a small tile-aligned N."""
    from sparkrdma_tpu.config import ShuffleConf
    from sparkrdma_tpu.exchange.partitioners import range_partitioner
    from sparkrdma_tpu.exchange.protocol import ShuffleExchange

    n = 1 << 13
    mesh = Mesh(np.asarray(topo.devices[:1], dtype=object), ("shuffle",))
    conf = ShuffleConf(slot_records=n, val_words=23, geometry_classes="fine",
                       pack_sort_min_payload=0, wide_sort_ride_words=0)
    ex = ShuffleExchange(mesh, "shuffle", conf)
    assert not ex.interpret and ex.sort_mode(25) == "wide"
    fn = ex._build_exec(num_parts=1, capacity=n, num_rounds=1,
                        out_capacity=n, record_words=25,
                        partitioner=range_partitioner(
                            np.zeros((0, 2), np.uint32)),
                        sort_key_words=2, donate_out=True, tight_out=True)
    cols = jax.ShapeDtypeStruct((25, n), jnp.uint32,
                                sharding=NamedSharding(mesh,
                                                       P(None, "shuffle")))
    compiled = fn.lower(cols, cols).compile()
    # the pooled output buffer is donated: aliased, not copied
    assert compiled.memory_analysis().alias_size_in_bytes > 0
