"""The plain references against the program at a tiny size on the CPU,
for both configurations, through the harness's whole run; the references
against numpy; and each configuration's control coming out not correct."""

import time

import jax
import numpy as np
import pytest

from perfbench import harness, registry
from perfbench_cells import FOUR_CHIP, ROOT, TINY, make_cell
from perfbench.gen import make_inputs, seed_words

#: the controls' sizes: enough records that 32-bit key prefixes repeat
#: (about n^2 / 2^33 pairs) for the sort's control to show
CONTROL = {"terasort_100b_1chip": 1 << 18, "repartition256_1chip": 65536,
           FOUR_CHIP: 1 << 16}
SEED = 2 ** 31 + 77   # more than 32 signed bits hold


def run_tiny(cell_name, seed=SEED, seconds=0.3, trace_dir=None, trace=False):
    cell = make_cell(cell_name)
    devices = jax.devices()[:cell.chips]
    return harness.run_cell(ROOT, cell, seed, seconds, trace, devices,
                            time.perf_counter(),
                            records_per_chip=TINY[cell_name],
                            trace_dir=trace_dir)


@pytest.mark.parametrize("cell_name", sorted(TINY))
def test_program_matches_reference(cell_name, capsys):
    res = run_tiny(cell_name)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= 2
    assert list(res)[-1] == "checks"
    assert all(v["value"] == 0 for v in res["checks"].values())
    cell = make_cell(cell_name)
    assert set(res["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert all(v["value"] > 0 for v in res["metrics"].values())
    err = capsys.readouterr().err.strip().splitlines()
    # the numbers compared, with their limits, are the last stderr lines
    assert [ln.split()[2] for ln in err[-len(res["checks"]):]] == \
        list(res["checks"])


def _inputs(cell_name, seed=SEED, sizes=TINY):
    from sparkrdma_tpu.runtime.mesh import make_mesh

    cell = make_cell(cell_name)
    mesh = make_mesh(jax.devices()[:cell.chips], "x")
    xs = make_inputs(mesh, "x", cell.config, cell.traffic, seed,
                     sizes[cell_name])
    return cell, mesh, xs


def test_inputs_share_keys_and_repeat_by_seed():
    cell, _, xs = _inputs(FOUR_CHIP)
    a, b = np.asarray(xs[0]), np.asarray(xs[1])
    assert np.array_equal(a[:2], b[:2]) and not np.array_equal(a[2:], b[2:])
    keys = (a[0].astype(np.uint64) << np.uint64(32)) | a[1]
    assert np.unique(keys).size == keys.size          # unique scheme
    again = np.asarray(_inputs(FOUR_CHIP)[2][0])
    assert np.array_equal(a, again)
    other = np.asarray(_inputs(FOUR_CHIP, seed=5)[2][0])
    assert not np.array_equal(a, other)
    _, _, ys = _inputs("repartition256_1chip")
    assert not np.array_equal(np.asarray(ys[0]), np.asarray(ys[1]))


def test_sorted_reference_is_numpy_sort():
    cell, mesh, xs = _inputs(FOUR_CHIP)
    ref = registry.reference(ROOT, "sorted").Reference(
        cell.config, jax.devices()[0], 4)
    a = np.asarray(xs[1])
    want = np.lexsort((a[1], a[0]))
    assert np.array_equal(np.asarray(ref.order(xs[1], 1)), want)


def test_partitioned_reference_hash_is_numpy_hash():
    ref_mod = registry.reference(ROOT, "partitioned")
    rng = np.random.default_rng(0)
    k = rng.integers(0, 2 ** 32, size=(2, 1000), dtype=np.uint32)
    h = np.zeros(1000, np.uint64)
    for w in range(2):
        h = ((h ^ k[w]) * np.uint64(2654435761)) & np.uint64(0xFFFFFFFF)
    h ^= h >> np.uint64(16)
    got = np.asarray(ref_mod.partition_of(tuple(k), 256))
    assert np.array_equal(got, (h % np.uint64(256)).astype(np.int64))


@pytest.mark.parametrize("cell_name", ["terasort_100b_1chip",
                                       "repartition256_1chip",
                                       FOUR_CHIP])
def test_control_is_not_correct(cell_name):
    """The control (the reference with one stated guarantee broken: a
    32-bit sort of 64-bit keys; slots of the mean partition size that
    drop the overflow) fails a number on three seeds."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    for seed in (1, 2, 3):
        cell, mesh, xs = _inputs(cell_name, seed, CONTROL)
        mod = registry.reference(ROOT, cell.config["reference"])
        ref = mod.Reference(cell.config, jax.devices()[0], cell.chips)
        out, totals = ref.control_output(
            xs[0], 0, NamedSharding(mesh, P("x")))
        got = ref.numbers(xs[0], 0, out, totals)
        assert any(got[k] > mod.LIMITS[k] for k in mod.LIMITS), got
        sound = ref.numbers(xs[0], 0, *_reference_output(ref, mod, xs[0],
                                                         mesh))
        assert all(sound[k] <= mod.LIMITS[k] for k in mod.LIMITS), sound


def _reference_output(ref, mod, x, mesh):
    """The reference's own answer in the program's layout: reads 0."""
    import jax.numpy as jnp

    chips = mesh.size
    n = x.shape[1]
    if hasattr(ref, "order"):
        perm = np.asarray(ref.order(x, 0))
        out = np.asarray(x)[:, perm]
    else:
        a = np.asarray(x)
        pid = np.asarray(mod.partition_of(tuple(a[:ref.kw]), ref.parts))
        out = a[:, np.argsort(pid, kind="stable")]
    return jnp.asarray(out), jnp.full((chips,), n // chips, jnp.int32)


def test_seed_words_take_any_whole_number():
    assert seed_words(2 ** 40, 4).dtype == np.uint32
    assert not np.array_equal(seed_words(-3, 4), seed_words(3, 4))
