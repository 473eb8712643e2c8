"""Pallas remote-DMA all-to-all — the kernel-level transport backend.

This is the closest structural analogue to SparkRDMA's data plane in the
whole framework: where ``RdmaChannel.rdmaReadInQueue`` posts one-sided
work requests that the NIC DMAs directly between registered buffers
(src/main/java/org/apache/spark/shuffle/rdma/RdmaChannel.java), this
module posts ``pltpu.make_async_remote_copy`` descriptors that the TPU's
ICI DMA engines execute directly between per-chip HBM buffers — no
compute-core involvement in the transfer, completion signaled on
semaphores (the CQ analogue), per-peer send/recv semaphore arrays (the
QP-pair analogue).

Default transport remains XLA's ``lax.all_to_all`` (the compiler schedules
and overlaps it well); this backend exists because the reference's
defining capability is a *user-controlled* one-sided transport — explicit
descriptors allow schedules XLA will not emit. Two such schedules ARE
implemented here (round 8):

* ``make_ring_all_to_all`` — the single-round kernel: direct pairwise
  sends, P-1 remote copies per device, chunk for peer ``d`` written
  straight into ``recv[my_id]`` on ``d`` (every chunk crosses the fabric
  once; the ICI torus routes it), preceded by a barrier-semaphore
  readiness handshake (the rdma_cm connect/accept analogue).
* ``make_ring_exchange`` — the multi-round fused kernel behind
  ``ShuffleConf(ring_fused=True)``: one pallas program carries ALL
  exchange rounds. Round ``k+1``'s remote DMAs are started before round
  ``k``'s completions are waited (double-buffered send/recv semaphore
  banks, parity ``r % 2``), so the fabric stays busy while the consumer
  folds the previous round's chunks; the barrier handshake is hoisted to
  once per exchange instead of once per round; and the size-exchange
  rides a prefix lane of round 0's payload (protocol.py embeds
  ``dev_counts`` in the first slot column, so no separate counts
  ``all_to_all`` serializes ahead of the payload).

The parity-bank schedule assumes DMA deliveries between a fixed
(src, dst) device pair complete in posting order — true of the ICI
fabric's virtual-channel ordering, and trivially true of interpret mode.
Without that, bytes from round ``r+2`` (same bank as ``r``) could
satisfy round ``r``'s recv wait; ``scripts/ring_pod.py`` is the
execution gate that would catch any violation on real hardware.

Coverage status (round 8, measured): parity/golden tests run both
kernels in interpret mode on the 8-device CPU mesh (the HLO interpreter
cannot lower collective semaphores, so the barrier handshake is
interpret-skipped by necessity, not choice) — the fused kernel is pinned
bit-equal to per-round ``lax.all_to_all`` across 1/2/5 rounds, ragged
last rounds, and the 1-device degenerate case, and the full
``transport="pallas_ring"`` exchange is pinned bit-equal to
``transport="xla"`` for repartition, terasort, and streaming-regime
shapes. ``tests/test_tpu_compile.py`` compiles the fused kernel for a
described v5e at record widths 13 and 25 (Mosaic lowering, no chip),
and ``chip_smoke.py --chips 4`` runs a 100-byte TeraSort through it on
four chips against the xla transport. ``scripts/ring_smoke.py``
exercises the Mosaic-lowered local-DMA + semaphore path on a single
real chip; the POD-READINESS pack is ``scripts/ring_pod.py``: where
``len(jax.devices()) >= 2`` it executes the remote-DMA + barrier legs —
including a fused multi-round leg — end to end and asserts parity
against ``lax.all_to_all``; with fewer chips it refuses loudly instead
of pretending.
"""

from __future__ import annotations

import zlib
from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from sparkrdma_tpu.runtime.mesh import mesh_interpret

#: the (sublane, lane) tile of a uint32 array in TPU memory. Mosaic
#: slices an HBM ref only along its untiled leading dims, so every block
#: the ring kernels DMA must fill whole tiles in its two minor dims.
_TILE = (8, 128)


def _tile_blocks(slots: jax.Array) -> jax.Array:
    """Pad the two minor dims (record width W, slot capacity C — the
    ``[..., W, C]`` of every per-destination block) up to whole
    ``_TILE`` tiles. It removes a block slice Mosaic refuses
    (W % 8 != 0, e.g. the 100-byte W=25) and any reshape across
    unaligned minor dims, which XLA would lower as an element-granular
    relayout (hundreds of MB of code at 16M records). It is not free:
    this pad and the slice that drops it after the kernel each write a
    full copy of the slot tensor (32 of 25 rows at W=25) on every ring
    exchange. The wire protocol (counts lane, slot order) never sees
    the pad."""
    w, c = slots.shape[-2:]
    pad = [(0, 0)] * (slots.ndim - 2) + [(0, -w % _TILE[0]),
                                         (0, -c % _TILE[1])]
    return jnp.pad(slots, pad) if any(p for _, p in pad) else slots


def derive_collective_id(key) -> int:
    """Map an exec-cache key to a stable barrier-semaphore id.

    Two live exchanges (multi-shuffle) must not share a barrier
    semaphore — a device entering shuffle B's kernel would satisfy a
    peer still waiting in shuffle A's handshake. The id is derived from
    the exec-cache key so the same compiled program always reuses the
    same semaphore (cache-friendly) while distinct plans get distinct
    ids with high probability. Mosaic's collective-id space is small;
    1..63 keeps clear of id 0 (reserved by some lowerings).
    """
    return 1 + zlib.crc32(repr(key).encode("utf-8")) % 63


def _a2a_kernel(send_ref, recv_ref, send_sem, recv_sem, local_sem, *,
                axis_name: str, num_devices: int, collective: bool):
    my = lax.axis_index(axis_name)

    if collective:
        # readiness handshake: signal every peer, wait for every peer
        barrier = pltpu.get_barrier_semaphore()
        for s in range(1, num_devices):
            peer = lax.rem(my + s, num_devices)
            pltpu.semaphore_signal(
                barrier, inc=1, device_id=peer,
                device_id_type=pltpu.DeviceIdType.LOGICAL)
        pltpu.semaphore_wait(barrier, num_devices - 1)

    # my own chunk never crosses the fabric (local blocks short-circuit
    # to file reads in the reference's fetcher, same idea)
    local = pltpu.make_async_copy(send_ref.at[my], recv_ref.at[my],
                                  local_sem)
    local.start()

    sends = []
    for s in range(1, num_devices):
        dst = lax.rem(my + s, num_devices)
        # one-sided: write my chunk for dst into dst's recv[my]
        rdma = pltpu.make_async_remote_copy(
            src_ref=send_ref.at[dst],
            dst_ref=recv_ref.at[my],
            send_sem=send_sem.at[dst],
            recv_sem=recv_sem.at[my],
            device_id=dst,
            device_id_type=pltpu.DeviceIdType.LOGICAL,
        )
        rdma.start()
        sends.append(rdma)

    local.wait()
    for rdma in sends:
        rdma.wait_send()
    # completions: one chunk per remote peer lands in recv[src]. DMA
    # semaphores are waited through a mirrored descriptor (it carries the
    # byte count to account), not a raw semaphore_wait.
    for s in range(1, num_devices):
        src = lax.rem(my - s + num_devices, num_devices)
        pltpu.make_async_remote_copy(
            src_ref=send_ref.at[src],
            dst_ref=recv_ref.at[src],
            send_sem=send_sem.at[src],
            recv_sem=recv_sem.at[src],
            device_id=src,
            device_id_type=pltpu.DeviceIdType.LOGICAL,
        ).wait_recv()


def _ring_exchange_kernel(send_ref, recv_ref, send_sem, recv_sem,
                          local_sem, *, axis_name: str, num_devices: int,
                          num_rounds: int, collective: bool):
    """All exchange rounds in one program, double-buffered.

    ``send_ref``/``recv_ref`` are ``[R, P, ...]``; round ``r`` uses
    semaphore bank ``r % 2`` so round ``r+1``'s DMAs are posted (and in
    flight on the fabric) before round ``r``'s completions are waited.
    See the module docstring for the (src, dst)-pair ordering assumption
    this parity scheme rests on.
    """
    my = lax.axis_index(axis_name)

    if collective:
        # readiness handshake — ONCE per exchange, not once per round:
        # after every peer has entered the kernel, all R rounds of
        # one-sided writes are safe because the recv buffers for every
        # round already exist on every peer.
        barrier = pltpu.get_barrier_semaphore()
        for s in range(1, num_devices):
            peer = lax.rem(my + s, num_devices)
            pltpu.semaphore_signal(
                barrier, inc=1, device_id=peer,
                device_id_type=pltpu.DeviceIdType.LOGICAL)
        pltpu.semaphore_wait(barrier, num_devices - 1)

    started = {}

    def start_round(r):
        bank = r % 2
        local = pltpu.make_async_copy(send_ref.at[r, my],
                                      recv_ref.at[r, my],
                                      local_sem.at[bank])
        local.start()
        remotes = []
        for s in range(1, num_devices):
            dst = lax.rem(my + s, num_devices)
            rdma = pltpu.make_async_remote_copy(
                src_ref=send_ref.at[r, dst],
                dst_ref=recv_ref.at[r, my],
                send_sem=send_sem.at[bank, dst],
                recv_sem=recv_sem.at[bank, my],
                device_id=dst,
                device_id_type=pltpu.DeviceIdType.LOGICAL,
            )
            rdma.start()
            remotes.append(rdma)
        started[r] = (local, remotes)

    def wait_round(r):
        bank = r % 2
        local, remotes = started.pop(r)
        local.wait()
        for rdma in remotes:
            rdma.wait_send()
        # completions: waited through mirrored descriptors (they carry
        # the byte count to account), not raw semaphore_waits.
        for s in range(1, num_devices):
            src = lax.rem(my - s + num_devices, num_devices)
            pltpu.make_async_remote_copy(
                src_ref=send_ref.at[r, src],
                dst_ref=recv_ref.at[r, src],
                send_sem=send_sem.at[bank, src],
                recv_sem=recv_sem.at[bank, src],
                device_id=src,
                device_id_type=pltpu.DeviceIdType.LOGICAL,
            ).wait_recv()

    # the overlap schedule: round r+1 is posted before round r is waited,
    # so exactly one round of DMAs is always in flight behind the one
    # being folded (R static at trace time — unrolled, like the peers).
    start_round(0)
    for r in range(num_rounds):
        if r + 1 < num_rounds:
            start_round(r + 1)
        wait_round(r)


def make_ring_exchange(mesh, axis_name: str, num_rounds: int,
                       collective_id: int = 7,
                       metrics=None) -> Callable:
    """Build the fused multi-round exchange callable for shard_map.

    Takes per-device slots ``[R, P, ...]`` (``slots[r, d]`` destined for
    device ``d`` in round ``r``) and returns ``[R, P, ...]`` where
    ``out[r, s]`` is the chunk device ``s`` sent in round ``r`` — the
    same contract as R independent ``lax.all_to_all(split_axis=0,
    concat_axis=0, tiled=True)`` calls, but one kernel: one barrier,
    double-buffered rounds, fabric/fold overlap.

    The kernel is shape-generic over every trailing dim of the slots —
    it DMAs whatever ``[...]`` block the caller packed. Map-side
    combine and projection pushdown lean on exactly that: a projected
    exchange ships a narrower record width and a combined one packs
    compacted (ragged, count-prefixed) rounds, and both ride through
    here with NO wire-protocol change — the PR-7 size-exchange lane in
    ``exchange/protocol.py`` already carries the ragged per-destination
    counts in round 0's one-column prefix.
    """
    from sparkrdma_tpu.obs.metrics import MetricsRegistry

    if metrics is None:
        metrics = MetricsRegistry(enabled=False)
    num_devices = int(mesh.shape[axis_name])
    interpret = mesh_interpret(mesh)

    def exchange(slots: jax.Array) -> jax.Array:
        if slots.shape[0] != num_rounds:
            raise ValueError(
                f"fused exchange built for {num_rounds} rounds, "
                f"got slots with leading dim {slots.shape[0]}")
        if num_devices == 1:
            return slots
        metrics.counter("transport.ring.fused_kernels").inc()
        metrics.counter("transport.ring.fused_rounds").inc(num_rounds)
        metrics.counter("transport.ring.overlap_rounds").inc(
            max(num_rounds - 1, 0))
        kernel = partial(_ring_exchange_kernel, axis_name=axis_name,
                         num_devices=num_devices, num_rounds=num_rounds,
                         collective=not interpret)
        tiled = _tile_blocks(slots)
        out = pl.pallas_call(
            kernel,
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            out_shape=jax.ShapeDtypeStruct(tiled.shape, tiled.dtype,
                                           vma=frozenset({axis_name})),
            scratch_shapes=[
                # parity banks: [2, P] send/recv completions per round
                pltpu.SemaphoreType.DMA((2, num_devices)),
                pltpu.SemaphoreType.DMA((2, num_devices)),
                pltpu.SemaphoreType.DMA((2,)),  # local copies, per bank
            ],
            compiler_params=pltpu.CompilerParams(
                has_side_effects=True,
                collective_id=collective_id,
            ),
            interpret=interpret,
        )(tiled)
        return out[..., :slots.shape[-2], :slots.shape[-1]]

    return exchange


def make_ring_all_to_all(mesh, axis_name: str,
                         collective_id: int = 7,
                         metrics=None) -> Callable:
    """Build the per-device all-to-all callable for use under shard_map.

    Takes per-device slots ``[P, ...]`` (entry ``d`` destined for device
    ``d``) and returns ``[P, ...]`` where entry ``s`` is the chunk sent by
    device ``s`` — the same contract as ``lax.all_to_all(split_axis=0,
    concat_axis=0, tiled=True)`` on a dest-major slot tensor.

    ``metrics`` counts embedded kernel instances at trace time (one per
    round per compiled program) — the host-visible proxy for how much
    work runs on this transport.
    """
    from sparkrdma_tpu.obs.metrics import MetricsRegistry

    if metrics is None:
        metrics = MetricsRegistry(enabled=False)
    num_devices = int(mesh.shape[axis_name])
    interpret = mesh_interpret(mesh)

    def a2a(slots: jax.Array) -> jax.Array:
        if num_devices == 1:
            return slots
        metrics.counter("transport.ring.kernels").inc()
        kernel = partial(_a2a_kernel, axis_name=axis_name,
                         num_devices=num_devices,
                         collective=not interpret)
        tiled = _tile_blocks(slots)
        out = pl.pallas_call(
            kernel,
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            out_shape=jax.ShapeDtypeStruct(tiled.shape, tiled.dtype,
                                           vma=frozenset({axis_name})),
            scratch_shapes=[
                pltpu.SemaphoreType.DMA((num_devices,)),  # send completions
                pltpu.SemaphoreType.DMA((num_devices,)),  # recv completions
                pltpu.SemaphoreType.DMA,                  # local copy
            ],
            compiler_params=pltpu.CompilerParams(
                has_side_effects=True,
                collective_id=collective_id,
            ),
            interpret=interpret,
        )(tiled)
        return out[..., :slots.shape[-2], :slots.shape[-1]]

    return a2a


__all__ = ["make_ring_all_to_all", "make_ring_exchange",
           "derive_collective_id"]
