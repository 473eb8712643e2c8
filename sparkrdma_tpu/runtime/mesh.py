"""Per-process mesh runtime — the ``RdmaNode`` analogue.

SparkRDMA keeps exactly one ``RdmaNode`` per JVM (src/main/java/org/apache/
spark/shuffle/rdma/RdmaNode.java §ctor): it opens one verbs context, binds an
rdma_cm listener, owns the registered-buffer pool, and hands out cached
``RdmaChannel`` connections to peers. On TPU none of that exists as user
code — the ICI links are static and brought up by the runtime — so the
equivalent object owns:

- the ``jax.sharding.Mesh`` over the shuffle axis (one shuffle partition per
  device, the BASELINE north star), replacing the per-peer QP/channel cache;
- the :class:`~sparkrdma_tpu.hbm.slot_pool.SlotPool`, replacing
  ``RdmaBufferManager``;
- process/topology introspection, replacing ``RdmaShuffleManagerId``'s
  (host, port) identity.

There is deliberately no connect/accept path: where ``RdmaNode.getRdmaChannel``
dials and caches a connection (§getRdmaChannel, with maxConnectionAttempts
retries), ``MeshRuntime`` just validates that the peer is a mesh coordinate.
"""

from __future__ import annotations

import dataclasses
import os
import socket
from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from sparkrdma_tpu.config import ShuffleConf

#: Canonical name of the shuffle mesh axis. Every collective in
#: :mod:`sparkrdma_tpu.exchange` runs over this axis.
SHUFFLE_AXIS = "shuffle"


def make_mesh(
    devices: Optional[Sequence[jax.Device]] = None,
    axis_name: str = SHUFFLE_AXIS,
) -> Mesh:
    """Build a 1-D shuffle mesh over ``devices`` (default: all local devices).

    The 1-D shape matches the reference's flat peer set: SparkRDMA addresses
    every executor by (host, port) with no topology hierarchy. Multi-host and
    multi-slice topologies still present as one flat axis here; the staged
    intra-host/inter-host exchange is selected per shuffle with
    ``ShuffleConf(transport="hierarchical")``
    (:mod:`sparkrdma_tpu.exchange.hierarchical`).
    """
    if devices is None:
        devices = jax.devices()
    devs = np.asarray(devices, dtype=object)
    return Mesh(devs, (axis_name,))


def mesh_interpret(mesh: Mesh) -> bool:
    """Run this mesh's Pallas kernels in interpret mode?

    Follows the devices the kernels will run on — compiled on TPU,
    interpreted on anything else (the forced-host CPU test mesh) — and
    never the process's default backend, so a mesh of described TPU
    devices compiles the real kernel and a CPU mesh never silently
    pretends to."""
    return mesh.devices.flat[0].platform != "tpu"


@dataclasses.dataclass(frozen=True)
class ManagerId:
    """Identity of one shuffle participant — ``RdmaShuffleManagerId`` analogue.

    The reference identifies a peer by (host, port, BlockManagerId)
    (src/main/java/org/apache/spark/shuffle/rdma/RdmaShuffleManagerId.java);
    on a mesh, identity is (process_index, mesh coordinate).
    """

    process_index: int
    device_index: int

    def __str__(self) -> str:  # matches the reference's host:port logging style
        return f"proc{self.process_index}/dev{self.device_index}"


class MeshRuntime:
    """One per process; owns mesh + pool, like one RdmaNode per JVM."""

    def __init__(
        self,
        conf: Optional[ShuffleConf] = None,
        devices: Optional[Sequence[jax.Device]] = None,
        axis_name: str = SHUFFLE_AXIS,
    ):
        self.conf = conf or ShuffleConf()
        self.mesh = make_mesh(devices, axis_name)
        self.axis_name = axis_name
        # Import here to avoid a cycle (hbm imports config only).
        from sparkrdma_tpu.hbm.slot_pool import SlotPool

        # RdmaNode ctor preallocates+registers the buffer pool up front; same.
        self.pool = SlotPool(self.conf)

    # ------------------------------------------------------------------
    # topology introspection
    # ------------------------------------------------------------------
    @property
    def num_partitions(self) -> int:
        """World size of the shuffle axis = number of shuffle partitions."""
        return int(self.mesh.shape[self.axis_name])

    @property
    def devices(self) -> Tuple[jax.Device, ...]:
        return tuple(self.mesh.devices.flat)

    @property
    def process_index(self) -> int:
        """This process's rank in the multi-host job (0 single-host).

        The host half of :class:`ManagerId` — stamped into every journal
        span (``ExchangeSpan.process_index``) and into per-host journal
        file names via the ``{process}`` placeholder in
        ``ShuffleConf.metrics_sink``.
        """
        return int(jax.process_index())

    @property
    def process_count(self) -> int:
        """Number of host processes in the job (1 single-host)."""
        return int(jax.process_count())

    def process_identity(self) -> dict:
        """Stable identity of this host process, as stamped into every
        ``{"kind": "heartbeat"}`` journal line (obs.rollup): the
        multi-host rank pair plus the (host, pid) a reference
        ``RdmaShuffleManagerId`` would carry. JSON-ready."""
        return {
            "process_index": self.process_index,
            "host_count": self.process_count,
            "host": socket.gethostname(),
            "pid": os.getpid(),
        }

    def manager_id(self, device_index: int) -> ManagerId:
        d = self.devices[device_index]
        return ManagerId(process_index=d.process_index, device_index=device_index)

    def local_device_indices(self) -> Tuple[int, ...]:
        """Mesh coordinates owned by this process (multi-host case)."""
        me = jax.process_index()
        return tuple(
            i for i, d in enumerate(self.devices) if d.process_index == me
        )

    # ------------------------------------------------------------------
    # sharding helpers
    # ------------------------------------------------------------------
    def sharding(self, *spec) -> NamedSharding:
        """NamedSharding over the shuffle axis; default shards leading dim."""
        if not spec:
            spec = (self.axis_name,)
        return NamedSharding(self.mesh, P(*spec))

    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    def shard_rows(self, x) -> jax.Array:
        """Place host data with rows split across the shuffle axis.

        Uses ``make_array_from_callback`` so each process materializes
        only its addressable shards — the same call works single-process
        and multi-host (where ``device_put`` of a globally-sharded array
        would fail on non-addressable devices).
        """
        x = np.ascontiguousarray(x)
        return jax.make_array_from_callback(
            x.shape, self.sharding(), lambda idx: x[idx])

    def shard_records(self, rows) -> jax.Array:
        """Host row-major records ``[N, W]`` -> device record batch.

        Device-side record batches are COLUMNAR: ``u32[W, N]`` sharded
        over ``N`` (structure-of-arrays). TPU tiles pad the minor
        dimension to 128 lanes, so a row-major ``[N, 4]`` array can cost
        32x its logical size and row-gathers use 4 of 128 lanes; storing
        each record word as a contiguous ``[N]`` vector makes every
        kernel a full-lane operation. Hosts still speak rows (the
        reference's record framing); this is the transpose boundary.
        """
        cols = np.ascontiguousarray(np.ascontiguousarray(rows).T)
        return jax.make_array_from_callback(
            cols.shape, self.sharding(None, self.axis_name),
            lambda idx: cols[idx])

    def host_rows(self, cols) -> "np.ndarray":
        """Device columnar batch ``[W, N]`` -> host rows ``[N, W]``."""
        return np.ascontiguousarray(np.asarray(cols).T)

    # ------------------------------------------------------------------
    # lifecycle
    # ------------------------------------------------------------------
    def stop(self) -> None:
        """Free pooled slots — RdmaNode.stop (drain + dereg pools) analogue."""
        self.pool.clear()

    def __enter__(self) -> "MeshRuntime":
        return self

    def __exit__(self, *exc) -> None:
        self.stop()


__all__ = ["MeshRuntime", "ManagerId", "make_mesh", "SHUFFLE_AXIS"]
