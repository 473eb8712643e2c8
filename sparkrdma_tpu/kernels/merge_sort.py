"""Pallas merge-path sort — the fast device sort for large record batches.

SURVEY.md §7 hard-part 3 ("sort-merge in HBM at line rate") and the round-2
verdict's top task. The reference hands reduce-side key ordering to Spark's
``ExternalSorter`` (a disk-backed merge sort); here the analogous component
is a TPU-native two-phase sort over columnar records ``uint32[W, N]``:

1. **Run formation** (XLA): one batched ``lax.sort`` over contiguous
   chunks of ``L0`` records. XLA keeps each chunk VMEM-resident, so this
   costs ~1 HBM read+write plus the in-VMEM network — measured ~5x faster
   per byte than a monolithic ``lax.sort`` at 16M records
   (scripts/profile_sweep.py fastsort: 15.8ms vs 77ms chunked@32K).
2. **Merge stages** (Pallas): ``log2(N/L0)`` stages; stage ``s`` merges
   pairs of sorted runs of length ``R`` into runs of ``2R``. Each stage is
   ONE kernel pass over the array: for every output tile of ``T`` records,
   the host-precomputed *merge-path diagonal* (binary search on device,
   vectorized in XLA) gives the exact split ``(a, b)`` of the tile's
   sources; the kernel DMAs the two candidate windows ``A[a:a+T]`` and
   ``B[b:b+T]`` into VMEM, bitonic-merges them (both are sorted; reversed
   concatenation is bitonic), and writes the first ``T`` — a linear merge
   at HBM bandwidth instead of ``lax.sort``'s O(log^2) global passes.

MEASURED STATUS (v5e, 16M x 16B records, scripts/profile_sweep.py
mergepath): correct
compiled and in interpret mode, but slower than monolithic ``lax.sort``
(~387ms vs ~82ms): each stage's HBM traffic is indeed ~2 scans, but the
in-VMEM bitonic merge network (reverse 17 + merge 17 passes over the
2T-candidate buffer) costs ~40ms/stage, while XLA's own sort spends only
~6.6ms per run-doubling — its register-resident network is already near
the hardware's bitonic floor. The kernel therefore ships OPT-IN
(``ShuffleConf(fast_sort=True)``), fully tested, as the scaffold for
future tuning (fewer VMEM passes via Batcher merge without the reversal,
key-only networks with rank-based payload placement). Round 4's wider
measurement campaign (README "sort floor" study) generalized this
finding: EVERY comparator-expressible route — monolithic, batched
quota sample-sort, key+index sort with gather placement, run-copy DMA
partition kernels — converges on the same floor, because Mosaic
exposes no vector scatter and the grouping step of any partition
scheme is itself a comparator pass.

Records compare lexicographically over ALL ``W`` words (keys lead, payload
words break ties). Total order up to identical records makes every
merge-path split multiset-exact — no stability bookkeeping is needed, and
the result is still "sorted by the key words". Callers that need
equal-key arrival order preserved must use the stable ``lexsort_cols``.

Padding handling: rows with ``valid == False`` are lifted to all-ones
(0xFFFFFFFF...) so they sort to the tail as a block, then zeroed back
after the sort — the same contract as ``lexsort_cols``'s validity lead.

The kernel runs compiled on TPU and in interpret mode on CPU (tests);
the caller says which.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_FULL = np.uint32(0xFFFFFFFF)   # numpy scalar: kernels may close over it


def _lex_lt(a_words, b_words):
    """Lexicographic a < b over aligned word lists (uint32).

    Seeded from the first word (no boolean constants: Mosaic lacks an
    i8->i1 truncation for materialized bool tensors)."""
    lt = a_words[0] < b_words[0]
    eq = a_words[0] == b_words[0]
    for a, b in zip(a_words[1:], b_words[1:]):
        lt = lt | (eq & (a < b))
        eq = eq & (a == b)
    return lt


_LANES = 128   # TPU vector lane width: reshapes must keep a >=128 minor dim


def _xor_partner_grouped(g, s):
    """``out[.., j] = g[.., j XOR s]`` per 128-lane group, for
    power-of-two ``s < _LANES``; ``g: [.., groups, 128]``.

    Mosaic cannot reshape below the 128-lane minor dimension, so
    sub-lane partner exchange is done with two per-lane-group rolls and
    a parity select: for lanes with bit ``s`` clear the partner is ``j +
    s`` (the up-roll), else ``j - s`` (the down-roll). ``j XOR s`` never
    leaves its 128-lane group, so group-cyclic rolls are exact.
    """
    up = pltpu.roll(g, shift=_LANES - s, axis=g.ndim - 1)
    down = pltpu.roll(g, shift=s, axis=g.ndim - 1)
    lane = lax.broadcasted_iota(jnp.int32, g.shape, g.ndim - 1)
    return jnp.where((lane & s) == 0, up, down)


def _reverse_cols(cols, length):
    """Reverse ``cols: [W, length]`` along the record axis without
    ``rev`` (no Mosaic lowering): reversal = ``i -> i XOR (length-1)``,
    composed from one unconditional partner-swap per bit — reshape/stack
    half-swaps for scales >= 128, lane-group rolls below."""
    w = cols.shape[0]
    size = length
    blocks = 1
    while size > 1:
        half = size // 2
        if half >= _LANES:
            y = cols.reshape(w, blocks, 2, half)
            cols = jnp.stack([y[:, :, 1, :], y[:, :, 0, :]],
                             axis=2).reshape(w, length)
        else:
            g = cols.reshape(w, length // _LANES, _LANES)
            cols = _xor_partner_grouped(g, half).reshape(w, length)
        blocks *= 2
        size = half
    return cols


def _bitonic_merge_cols(cols, length, words):
    """Merge a bitonic sequence ``cols: [W, length]`` ascending in VMEM.

    ``length`` must be a power of two. Full-record comparator: the swap
    decision uses the leading ``words`` rows (the record; any rows
    below are tile padding); all W rows move together. Strides >=
    128 use reshape-pair compare-exchange; smaller strides exchange
    partners via lane rolls (Mosaic reshape limit).
    """
    w = cols.shape[0]
    stride = length // 2
    while stride >= _LANES:
        blocks = length // (2 * stride)
        x = cols.reshape(w, blocks, 2, stride)
        a, b = x[:, :, 0, :], x[:, :, 1, :]
        swap = _lex_lt([b[i] for i in range(words)],
                       [a[i] for i in range(words)])
        lo = jnp.where(swap, b, a)
        hi = jnp.where(swap, a, b)
        cols = jnp.stack([lo, hi], axis=2).reshape(w, length)
        stride //= 2
    # sub-lane strides: stay in [w, groups, 128] tiles throughout (flat
    # [1, length] boolean vectors have no Mosaic lowering)
    g = cols.reshape(w, length // _LANES, _LANES)
    lane = lax.broadcasted_iota(jnp.int32, g.shape[1:], 1)  # [groups, 128]
    while stride >= 1:
        partner = _xor_partner_grouped(g, stride)
        low = (lane & stride) == 0
        xw = [g[i] for i in range(words)]
        pw = [partner[i] for i in range(words)]
        p_lt_x = _lex_lt(pw, xw)                 # [groups, 128]
        x_lt_p = _lex_lt(xw, pw)
        # logical blend, not where-on-bools: a select with boolean
        # BRANCH values round-trips through i8 and Mosaic cannot
        # truncate i8 vectors back to i1
        take = (low & p_lt_x) | (~low & x_lt_p)
        g = jnp.where(take[None], partner, g)
        stride //= 2
    return g.reshape(w, length)


def chunk_sort_cols(cols: jax.Array, run: int) -> jax.Array:
    """Batched full-record sort of contiguous ``run``-sized chunks (XLA)."""
    w, n = cols.shape
    m = n // run
    x = cols.reshape(w, m, run)
    out = lax.sort(tuple(x[i] for i in range(w)), num_keys=w,
                   is_stable=False, dimension=1)
    return jnp.stack(out).reshape(w, n)


# ----------------------------------------------------------------------
# merge-path diagonal search (XLA, vectorized over all tiles of a stage)
# ----------------------------------------------------------------------
_Q = 128   # merge-path refinement quantum (the lane width)


def _merge_path_offsets(cols: jax.Array, n: int, run: int, tile: int) -> jax.Array:
    """For each output tile, how many of its pair's A-run elements precede
    the tile's diagonal — int32[n_tiles].

    Tile ``t`` of pair ``p = t // tpp`` starts at merged rank ``d = (t %
    tpp) * tile``. The returned ``a`` satisfies: the first ``d`` merged
    elements are exactly ``A[:a] ∪ B[:d-a]`` under the full-record total
    order (ties split arbitrarily — harmless, see module docstring).

    TPU cost shaping: gathers scan their OPERAND, so a classic binary
    search (log R serialized gather trips over the full array) costs
    ~20ms/stage at 16M records (measured). Instead: (1) a coarse search
    over 128-strided samples — a ~N/128 operand, gathers nearly free —
    finds ``qa = floor(a*/128)`` exactly, because the feasibility
    predicate ``A[a-1] <= B[d-a]`` at 128-multiple ``a`` touches only
    ``A[127 mod 128]`` and ``B[0 mod 128]`` positions (diagonals are
    128-multiples); (2) ONE batched gather pulls each tile's 128-wide
    refinement windows and a vectorized predicate+popcount finishes
    exactly. Two scans of the big operand total, instead of log R.
    """
    w = cols.shape[0]
    tpp = (2 * run) // tile                   # tiles per pair
    n_pairs = n // (2 * run)
    n_tiles = n // tile
    runs = cols[:, :n].reshape(w, n_pairs, 2 * run)

    pair = jnp.arange(n_tiles, dtype=jnp.int32) // tpp
    d = (jnp.arange(n_tiles, dtype=jnp.int32) % tpp) * tile

    # data-derived zero keeps the fori_loop carry's varying-manual-axes
    # type consistent under shard_map (constant init would be unvarying)
    vz = (cols[0, 0] & jnp.uint32(0)).astype(jnp.int32)
    lo = jnp.maximum(0, d - run) + vz         # a in [lo, hi]
    hi = jnp.minimum(d, run) + vz

    # ---- phase 1: coarse search on strided samples -------------------
    # sa127[q] = A[q*128 + 127], sb0[q] = B[q*128]; the predicate at
    # a = qa*128 is  A[qa*128 - 1] <= B[d - qa*128]  =
    #               sa127[qa - 1]  <= sb0[(d - a) / 128]
    nq = run // _Q
    sa127 = [runs[i][:, _Q - 1:run:_Q] for i in range(w)]  # [n_pairs, nq]
    sb0 = [runs[i][:, run::_Q] for i in range(w)]

    qlo = lo // _Q                            # qa in [qlo, qhi]
    qhi = hi // _Q

    def qgather(words, p, idx):
        return [words[i][p, idx] for i in range(w)]

    def qbody(_, lohi):
        qlo, qhi = lohi
        qa = (qlo + qhi + 1) // 2
        a = qa * _Q
        ai = jnp.clip(qa - 1, 0, nq - 1)
        bi = jnp.clip((d - a) // _Q, 0, nq - 1)
        a_vals = qgather(sa127, pair, ai)
        b_vals = qgather(sb0, pair, bi)
        ok = ~_lex_lt(b_vals, a_vals)         # A[a-1] <= B[d-a]
        ok = ok | (qa <= 0)
        # d - a == run (B exhausted below diagonal) only at qa == qlo,
        # which the search never probes (midpoint > qlo)
        new_qlo = jnp.where(ok, qa, qlo)
        new_qhi = jnp.where(ok, qhi, qa - 1)
        return new_qlo, new_qhi

    trips = max(1, int(math.log2(max(2, nq))) + 2)
    qlo, qhi = lax.fori_loop(0, trips, qbody, (qlo, qhi))
    a0 = jnp.clip(qlo * _Q, lo, hi)           # a* in [a0, a0 + 128]

    # ---- phase 2: exact refinement, one batched gather ---------------
    # predicate for a = a0 + k (k = 1..128):  A[a0 + k - 1] <= B[d - a0
    # - k]; A window = A[a0 : a0 + 128], B window = B[d - a0 - 128 :
    # d - a0] — both 128-contiguous. One flat take() per word gathers
    # every tile's two windows in a single operand scan.
    flat = [runs[i].reshape(-1) for i in range(w)]   # [n_pairs * 2R]
    k = jnp.arange(_Q, dtype=jnp.int32)[None, :]     # [1, 128]
    base_pair = pair * (2 * run)
    a_idx = base_pair[:, None] + jnp.clip(a0[:, None] + k, 0, run - 1)
    b_off = jnp.clip(d[:, None] - a0[:, None] - _Q + k, 0, run - 1)
    b_idx = base_pair[:, None] + run + b_off
    idx = jnp.concatenate([a_idx, b_idx], axis=1).reshape(-1)
    vals = [jnp.take(flat[i], idx, axis=0).reshape(n_tiles, 2 * _Q)
            for i in range(w)]
    awin = [v[:, :_Q] for v in vals]                 # A[a0 + k]
    bwin = [v[:, _Q:] for v in vals]                 # B[d - a0 - 128 + k]
    # feasible(a0 + k) for k>=1:  A[a0+k-1] <= B[d-a0-k]
    # = awin[k-1] <= bwin[128 - k]  -> align: compare awin[j] (j=k-1)
    # with bwin reversed at j: brev[j] = bwin[127 - j]
    brev = [v[:, ::-1] for v in bwin]
    ok = ~_lex_lt(brev, awin)                        # [n_tiles, 128]
    # guard k beyond the true range [lo, hi]
    kk = a0[:, None] + 1 + jnp.arange(_Q, dtype=jnp.int32)[None, :]
    ok = ok & (kk <= hi[:, None])
    # clipped A-indices (a0 + k - 1 > run-1) mean A exhausted: infeasible
    ok = ok & ((a0[:, None] + jnp.arange(_Q)[None, :]) <= run - 1)
    # feasibility is monotone in k: a* = a0 + count of feasible k
    a_star = a0 + jnp.sum(ok.astype(jnp.int32), axis=1)
    return jnp.clip(a_star, lo, hi).astype(jnp.int32)


# ----------------------------------------------------------------------
# the per-stage Pallas kernel
# ----------------------------------------------------------------------
def _window(cols_ref, win, tail, sems, start_aligned, shift, tile, w):
    """DMA an aligned ``[W, tile]`` window + its 128-wide tail, then
    realign to the true (unaligned) start entirely in VMEM.

    Mosaic constraints shape this: HBM DMA offsets must be 128-aligned,
    and ``pltpu.roll`` with a DYNAMIC shift is only correct on
    power-of-two lane lengths (measured: wrong on tile+128). So the
    window loads as two aligned pieces, each pow2-rolled, stitched with
    an iota select: out[j] = cols[start_aligned + shift + j] for
    j < tile.
    """
    cp_w = pltpu.make_async_copy(
        cols_ref.at[:, pl.ds(start_aligned, tile)], win, sems[0])
    cp_t = pltpu.make_async_copy(
        cols_ref.at[:, pl.ds(start_aligned + tile, 128)], tail, sems[1])
    cp_w.start()
    cp_t.start()
    cp_w.wait()
    cp_t.wait()
    main = pltpu.roll(win[...], shift=-shift, axis=1)
    tail_pad = jnp.concatenate(
        [tail[...], jnp.zeros((w, tile - 128), jnp.uint32)], axis=1)
    tail_shifted = pltpu.roll(tail_pad, shift=tile - shift, axis=1)
    iota = lax.broadcasted_iota(jnp.int32, (1, tile), 1)
    return jnp.where(iota < tile - shift, main, tail_shifted)


def _stage_kernel(aoff_ref, cols_ref, out_ref, a_win, a_tail, b_win,
                  b_tail, sem_a, sem_at, sem_b, sem_bt, *, run, tile, w,
                  words):
    """One output tile of one merge stage.

    ``cols_ref``: the full padded array [W, n + 2*tile] in HBM/ANY.
    ``out_ref``: VMEM block [W, tile] at tile t.
    ``a_win/b_win``: VMEM scratch [W, tile]; ``*_tail``: [W, 128].
    """
    n_tiles = pl.num_programs(0) - 2          # grid has two pad tiles
    t_raw = pl.program_id(0)
    is_pad = t_raw >= n_tiles
    # clamp instead of branching: pl.when around the whole body would put
    # pl.* primitives inside a cond, which the CPU interpreter rejects;
    # the pad tile computes a harmless real tile and overwrites its
    # output with padding at the end
    t = jnp.minimum(t_raw, n_tiles - 1)
    tpp = (2 * run) // tile
    p = t // tpp
    d = (t % tpp) * tile
    a = aoff_ref[t]
    b = d - a
    base = p * (2 * run)
    sa = a & 127
    sb = b & 127

    # pl.multiple_of: the 128-alignment of (a - sa) is arithmetic fact,
    # not something Mosaic's divisibility prover can see through & 127
    a_start = pl.multiple_of(base + (a - sa), 128)
    b_start = pl.multiple_of(base + run + (b - sb), 128)
    wa = _window(cols_ref, a_win, a_tail, (sem_a, sem_at), a_start, sa,
                 tile, w)
    wb = _window(cols_ref, b_win, b_tail, (sem_b, sem_bt), b_start, sb,
                 tile, w)

    iota = lax.broadcasted_iota(jnp.int32, (1, tile), 1)  # 2D for Mosaic
    a_valid = iota < (run - a)                           # rest of A-run
    b_valid = iota < (run - b)                           # rest of B-run
    ca = jnp.where(a_valid, wa, _FULL)
    cb = jnp.where(b_valid, wb, _FULL)
    # ascending ++ descending = bitonic
    cand = jnp.concatenate([ca, _reverse_cols(cb, tile)],
                           axis=1)                       # [W, 2*tile]
    merged = _bitonic_merge_cols(cand, 2 * tile, words)
    out_ref[...] = jnp.where(is_pad, _FULL, merged[:, :tile])


def _merge_stage(cols_padded: jax.Array, aoff: jax.Array, *, n: int,
                 run: int, tile: int, words: int,
                 interpret: bool) -> jax.Array:
    """Dispatch one merge stage; returns the new padded array
    [W, n + 2*tile].

    The trailing ``2*tile`` columns stay all-ones padding (aligned
    B-windows of the last pair may read up to ``tile + 128`` past the
    real region); the two extra grid steps re-emit padding blocks.
    """
    w = cols_padded.shape[0]
    n_tiles = n // tile

    kernel = functools.partial(_stage_kernel, run=run, tile=tile, w=w,
                               words=words)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_tiles + 2,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((w, tile), lambda t, aoff: (0, t)),
        scratch_shapes=[
            pltpu.VMEM((w, tile), jnp.uint32),
            pltpu.VMEM((w, 128), jnp.uint32),
            pltpu.VMEM((w, tile), jnp.uint32),
            pltpu.VMEM((w, 128), jnp.uint32),
            pltpu.SemaphoreType.DMA(()),
            pltpu.SemaphoreType.DMA(()),
            pltpu.SemaphoreType.DMA(()),
            pltpu.SemaphoreType.DMA(()),
        ],
    )

    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((w, n + 2 * tile), jnp.uint32),
        grid_spec=grid_spec,
        interpret=interpret,
    )(aoff, cols_padded)


# ----------------------------------------------------------------------
# public entry
# ----------------------------------------------------------------------
#: scoped VMEM the stage kernel holds per record word per tile column:
#: the double-buffered output block, both windows, and the in-kernel
#: values (rolled windows, the 2*tile candidate, merge temporaries).
#: Compiled for v5e, W=8 at tile 32768 asked for 18.81 MiB (~18.8 x 4 B
#: per word-column); 20 x 4 B leaves headroom.
_VMEM_BYTES_PER_WORD_COL = 20 * 4
#: share of Mosaic's 16 MiB default scoped-VMEM limit the tile may fill
_VMEM_BUDGET = 12 * 1024 * 1024


def _pick_tile(w: int) -> int:
    """Largest power-of-two tile (multiple of 128, at most 2^15) whose
    real scoped-VMEM footprint fits the budget."""
    tile = 1 << 15
    while _VMEM_BYTES_PER_WORD_COL * w * tile > _VMEM_BUDGET and tile > 128:
        tile //= 2
    return tile


def supports_fast_sort(n: int, run: int = 1 << 15) -> bool:
    """Fast path needs a power-of-two N with at least two runs."""
    return n >= 2 * run and (n & (n - 1)) == 0


def merge_sort_cols(
    cols: jax.Array,
    valid: Optional[jax.Array] = None,
    run: int = 1 << 15,
    tile: Optional[int] = None,
    interpret: bool = False,
) -> jax.Array:
    """Sort columnar records ``uint32[W, N]`` by full-record lexicographic
    order (ascending). See module docstring for the algorithm and the
    (non-)stability contract.

    ``valid``: bool[N] — invalid rows sort to the tail and are zeroed.
    ``run``: initial XLA-sorted run length (power of two).
    ``tile``: merge kernel tile (default: auto from VMEM budget).
    ``interpret``: run the merge kernel in Pallas interpret mode — the
    caller's choice (a test, or :func:`~sparkrdma_tpu.runtime.mesh.
    mesh_interpret` of the mesh it runs on), never inferred here.
    """
    w, n = cols.shape
    # the kernel's refs carry whole 8-row tiles: Mosaic refuses a
    # window slice of, e.g., the 25 rows of a 100-byte record
    rows = -(-w // 8) * 8
    if run < _Q or run & (run - 1):
        # the coarse search's 128-quantum and the window-tail stitch
        # both assume a pow2 run of at least one lane group
        raise ValueError(
            f"run must be a power of two >= {_Q}, got {run}")
    if not supports_fast_sort(n, run):
        raise ValueError(
            f"merge_sort_cols needs power-of-two N >= {2*run}, got {n}")
    if tile is None:
        tile = min(_pick_tile(rows), run)
    if run % tile:
        raise ValueError(f"run {run} must be a multiple of tile {tile}")

    if valid is not None:
        cols = jnp.where(valid[None, :], cols, _FULL)

    cols = chunk_sort_cols(cols, run)
    # padded work layout [rows, N + 2*tile]: aligned B-windows of the
    # last pair may read up to tile + 128 past the array; the pad stays
    # all-ones across stages. Rows past W ride along, never compared.
    padded = jnp.pad(jnp.concatenate(
        [cols, jnp.full((w, 2 * tile), _FULL, jnp.uint32)], axis=1),
        ((0, rows - w), (0, 0)))
    r = run
    while r < n:
        aoff = _merge_path_offsets(padded[:w], n, r, tile)
        padded = _merge_stage(padded, aoff, n=n, run=r, tile=tile,
                              words=w, interpret=interpret)
        r *= 2
    out = padded[:w, :n]

    if valid is not None:
        total = jnp.sum(valid.astype(jnp.int32))
        keep = lax.iota(jnp.int32, n)[None, :] < total
        out = jnp.where(keep, out, jnp.uint32(0))
    return out


__all__ = ["merge_sort_cols", "chunk_sort_cols", "supports_fast_sort"]
