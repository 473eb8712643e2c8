"""Splitter computation for range partitioning — sortByKey's sampler.

Spark's RangePartitioner (the partitioner a TeraSort/sortByKey job hands to
the shuffle; external to the reference plugin but required by its headline
workload) reservoir-samples each input partition, weights samples by
partition size, and picks num_parts-1 quantile boundaries. The TPU-native
version keeps the same statistics but SPMD-shaped: every device takes a
strided/pseudo-random sample of its local keys, the samples are
all-gathered over ICI (tiny), and every device computes identical quantile
splitters — no driver round-trip at all.
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from sparkrdma_tpu.kernels.sort import lexsort_records
from sparkrdma_tpu.utils.profiling import annotate, device_phase


def make_sampler(mesh: Mesh, axis_name: str, key_words: int,
                 samples_per_device: int, seed: int = 0) -> Callable:
    """Compiled step: global records -> replicated sample matrix.

    Sampling is uniform-random with replacement from each device's local
    records, seeded per device (``fold_in(seed, axis_index)``) so it is
    deterministic yet order-insensitive — the SPMD equivalent of Spark
    RangePartitioner's per-partition reservoir sample. A strided sample
    (the previous design) skews the splitters badly on pre-sorted or
    clustered input; random indices have no such failure mode, and
    with-replacement vs reservoir makes no difference to quantile
    estimates at these sample sizes.
    Returns ``uint32[mesh * samples_per_device, key_words]`` replicated.
    """

    @device_phase("sr_sample")
    def local_sample(records):
        # records: columnar [W, n_local]
        n = records.shape[1]
        dev = jax.lax.axis_index(axis_name)
        key = jax.random.fold_in(jax.random.PRNGKey(seed), dev)
        idx = jax.random.randint(key, (samples_per_device,), 0, max(n, 1))
        sample = jnp.stack(
            [jnp.take(records[w], idx) for w in range(key_words)], axis=1
        )  # [samples, key_words] — tiny, row-major is fine
        # all_gather so every device can compute identical splitters
        gathered = jax.lax.all_gather(sample, axis_name, tiled=True)
        return gathered

    fn = shard_map(
        local_sample,
        mesh=mesh,
        in_specs=(P(None, axis_name),),
        out_specs=P(),  # replicated by the all_gather
        check_vma=False,  # VMA can't statically infer all_gather replication
    )
    return jax.jit(fn)


def compute_splitters(samples: np.ndarray, num_parts: int) -> np.ndarray:
    """Quantile boundaries from a gathered key sample.

    Returns ``uint32[num_parts - 1, key_words]`` ascending — the input to
    :func:`sparkrdma_tpu.exchange.partitioners.range_partitioner`.
    """
    with annotate("shuffle:splitters"):
        samples = np.asarray(samples)
        if samples.ndim != 2:
            raise ValueError("samples must be [n, key_words]")
        n, kw = samples.shape
        if n == 0 or num_parts < 2:
            return np.zeros((max(0, num_parts - 1), kw), dtype=np.uint32)
        srt = np.asarray(lexsort_records(jnp.asarray(samples), kw))
        idx = (np.arange(1, num_parts) * n) // num_parts
        return srt[idx].astype(np.uint32)


__all__ = ["make_sampler", "compute_splitters"]
