"""Host staging: native buffer pool + pipelined spill (ctypes bridge).

The reference's entire data plane rests on native host components
(SURVEY.md §2.5): libdisni/libibverbs post work requests against
pre-registered host buffers, and RdmaMappedFile serves mmap'd shuffle
files without copying. On TPU the fabric side of that is XLA's job, but
the *host* side — staging map outputs to host RAM/disk so they survive
process death, and feeding them back without re-running the map stage —
still wants native code. This module bridges to ``native/staging.cpp``:

- :class:`HostBufferPool` — aligned, power-of-two size-classed host
  buffers (``RdmaBufferManager.get/put`` semantics, same class rule as
  the device :class:`~sparkrdma_tpu.hbm.slot_pool.SlotPool`);
- :class:`SpillWriter` — a background writer thread with a bounded queue
  (the bytes-in-flight throttle) persisting buffers to disk while the
  caller keeps computing — the overlap the reference gets from async
  work-request completion;
- graceful **fallback to numpy/stdlib** when the shared library can't be
  built (conf.use_native_staging=False forces the fallback).

The library is built on demand with ``make -C sparkrdma_tpu/native`` the
first time it is needed; failures degrade silently to the fallback so the
framework never requires a toolchain at runtime.
"""

from __future__ import annotations

import ctypes
import logging
import os
import struct
import subprocess
import threading
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

log = logging.getLogger("sparkrdma_tpu.staging")


def _count_spill(nbytes: int) -> None:
    """Record one host-staging spill in the process-wide registry.

    Module-level functions and standalone SpillWriters have no manager
    (and therefore no per-manager registry) in reach, so spills land in
    :func:`~sparkrdma_tpu.obs.metrics.global_registry`; the SPI layer
    folds the cumulative count into each exchange span at emit time.
    """
    from sparkrdma_tpu.obs.metrics import global_registry
    from sparkrdma_tpu.obs.timeline import record_active

    reg = global_registry()
    reg.counter("staging.spills").inc()
    reg.counter("staging.spill_bytes").inc(nbytes)
    # also a timeline event in whichever manager's span is active, so a
    # mid-read spill shows up in the journal's events array / the trace
    record_active("staging:spill", bytes=nbytes)


def spill_count() -> int:
    """Cumulative process-wide spill submissions (journal field source)."""
    from sparkrdma_tpu.obs.metrics import global_registry

    return int(global_registry().counter("staging.spills").value)

# ---------------------------------------------------------------------
# optional spill/checkpoint compression (round 5)
#
# The reference's hot read loop is "take stream -> DECOMPRESS ->
# deserialize" because Spark compresses every shuffle block (lz4/zstd)
# and SparkRDMA serves those compressed bytes as-is (SURVEY.md §3.3).
# Here compression is a STORAGE-side option only — spill runs and
# checkpoints — because the fabric-side decision went the other way,
# measured (scripts/compress_note.py, v5e round 5): the exchange+sort
# pipeline sustains ~GB/s/chip while stdlib zlib decompresses at
# ~0.1-0.3 GB/s/core, so fabric-side compression would bottleneck the
# data plane ~10x (measured through a deployment that no longer exists;
# not measured on the current chip). Files carry a
# self-describing header so readers auto-detect; raw files stay
# bit-identical to rounds 1-4 (the codec is opt-in via
# ShuffleConf.compression).
# ---------------------------------------------------------------------

_CODEC_MAGIC = b"SRZC"
_CODEC_IDS = {"zlib": 1, "lzma": 2}
_HDR = struct.Struct("<4sBQ")        # magic, codec id, raw nbytes

# ---------------------------------------------------------------------
# CRC32 integrity trailer (chaos-plane round)
#
# Spill blobs and checkpoint shards used to carry a magic header but no
# integrity check: a bit flipped on disk decoded into garbage records.
# Every file written here now ends with an 8-byte trailer — magic +
# CRC32 of everything before it — appended to the byte stream BEFORE the
# writer runs, so the native (sr_write_file / spooler) and numpy
# (tofile) paths stay bit-identical. Readers auto-detect: a file of
# exactly the expected payload size is a legacy (pre-trailer) file and
# reads as before; payload + 8 bytes with the trailer magic verifies the
# CRC and maps a mismatch onto read_array's documented OSError contract.
# ---------------------------------------------------------------------

_CRC_MAGIC = b"SRC1"
_CRC_TRAILER = struct.Struct("<4sI")  # magic, crc32 of preceding bytes


def _as_u8(arr: np.ndarray) -> np.ndarray:
    """Flat uint8 view of a contiguous array (no copy)."""
    return np.ascontiguousarray(arr).reshape(-1).view(np.uint8)


def crc_frame(arr: np.ndarray) -> np.ndarray:
    """``payload + CRC32 trailer`` as one contiguous uint8 buffer.

    One copy of the payload — the price of handing a single buffer to
    the (async) native writers so both write paths emit identical bytes.
    """
    import zlib

    flat = _as_u8(arr)
    trailer = np.frombuffer(
        _CRC_TRAILER.pack(_CRC_MAGIC, zlib.crc32(flat) & 0xFFFFFFFF),
        np.uint8)
    return np.concatenate([flat, trailer])


def crc_frame_into(arr: np.ndarray, pool: "HostBufferPool"):
    """Stage ``payload + CRC32 trailer`` into a pooled host-buffer lease.

    Bit-identical frame bytes to :func:`crc_frame`, but the staging
    buffer comes from a :class:`HostBufferPool` instead of a fresh
    ``np.concatenate`` allocation — steady-state spilling through a pool
    is allocation-free (the rss-creep fix). Returns ``(frame, lease)``:
    ``frame`` is the exact-size uint8 view to hand to a writer, and the
    caller must ``lease.release()`` once the write has landed
    (:class:`SpillWriter` does this at drain).
    """
    import zlib

    flat = _as_u8(arr)
    n = flat.nbytes + _CRC_TRAILER.size
    lease = pool.get(n)
    frame = lease.view(np.uint8, (n,))
    frame[:flat.nbytes] = flat
    frame[flat.nbytes:] = np.frombuffer(
        _CRC_TRAILER.pack(_CRC_MAGIC, zlib.crc32(flat) & 0xFFFFFFFF),
        np.uint8)
    return frame, lease


def verify_crc(payload: np.ndarray, trailer: bytes, path: str) -> None:
    """Check an 8-byte trailer against the payload; OSError on mismatch."""
    import zlib

    magic, crc = _CRC_TRAILER.unpack(trailer)
    if magic != _CRC_MAGIC:
        raise OSError(f"spill file {path}: trailing bytes are not a CRC "
                      "trailer — truncated or corrupt")
    actual = zlib.crc32(_as_u8(payload)) & 0xFFFFFFFF
    if actual != crc:
        raise OSError(
            f"spill file {path} failed CRC32 verification (stored "
            f"{crc:#010x}, computed {actual:#010x}) — corrupt")


def compress_array(arr: np.ndarray, codec: str, level: int = 1) -> bytes:
    """Header + compressed bytes of a contiguous array."""
    raw = np.ascontiguousarray(arr).tobytes()
    if codec == "zlib":
        import zlib

        blob = zlib.compress(raw, level)
    elif codec == "lzma":
        import lzma

        blob = lzma.compress(raw, preset=level)
    else:
        raise ValueError(f"unknown compression codec {codec!r}")
    return _HDR.pack(_CODEC_MAGIC, _CODEC_IDS[codec], len(raw)) + blob


def decompress_blob(blob: bytes) -> bytes:
    """Inverse of :func:`compress_array` (returns the raw bytes)."""
    if len(blob) < _HDR.size:
        # a truncated file can be shorter than the 13-byte header; keep
        # the documented OSError contract instead of struct.error
        raise OSError(f"not a compressed spill blob ({len(blob)} bytes "
                      "is shorter than the codec header) — truncated")
    magic, cid, raw_n = _HDR.unpack_from(blob)
    if magic != _CODEC_MAGIC:
        raise OSError("not a compressed spill blob (bad magic)")
    body = blob[_HDR.size:]
    # truncated/flipped compressed bytes surface as codec-specific
    # exceptions (zlib.error, lzma.LZMAError); re-raise as OSError so
    # callers see read_array's documented corruption contract instead of
    # needing to know which codec wrote the file
    if cid == _CODEC_IDS["zlib"]:
        import zlib

        try:
            raw = zlib.decompress(body)
        except zlib.error as e:
            raise OSError(f"corrupt spill blob: {e}") from e
    elif cid == _CODEC_IDS["lzma"]:
        import lzma

        try:
            raw = lzma.decompress(body)
        except lzma.LZMAError as e:
            raise OSError(f"corrupt spill blob: {e}") from e
    else:
        raise OSError(f"unknown codec id {cid} in spill header")
    if len(raw) != raw_n:
        raise OSError(f"decompressed {len(raw)} bytes, header said "
                      f"{raw_n} — corrupt spill blob")
    return raw

# native/ ships inside the package (pyproject package-data) so installed
# wheels can build the library on demand too, not just source checkouts.
_NATIVE_DIR = Path(__file__).resolve().parent.parent / "native"
_LIB_PATH = _NATIVE_DIR / "build" / "libsparkstaging.so"

#: opt into a sanitizer-instrumented library flavor: "" (default,
#: plain), "tsan", or "asan". The sanitizer test legs set this in child
#: processes (the runtime must be LD_PRELOADed before python starts, so
#: a flavored parent process is not a thing).
_FLAVOR_ENV = "SPARKRDMA_NATIVE_FLAVOR"
_FLAVORS = ("", "tsan", "asan")

_lib_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None      # guarded-by: _lib_lock
_lib_attempted = False                  # guarded-by: _lib_lock


def native_flavor() -> str:
    """The sanitizer flavor this process is configured for ('' = plain).

    An unknown value degrades to plain with a warning — same philosophy
    as every other native-path failure here: never take down the job
    over instrumentation.
    """
    flavor = os.environ.get(_FLAVOR_ENV, "").strip()
    if flavor not in _FLAVORS:
        log.warning("unknown %s=%r (expected one of %s); using plain "
                    "library", _FLAVOR_ENV, flavor, "/".join(_FLAVORS[1:]))
        return ""
    return flavor


def _flavored_lib_path(flavor: str) -> Path:
    name = (f"libsparkstaging-{flavor}.so" if flavor
            else "libsparkstaging.so")
    return _NATIVE_DIR / "build" / name


def _declare(lib: ctypes.CDLL) -> ctypes.CDLL:
    lib.sr_alloc.restype = ctypes.c_void_p
    lib.sr_alloc.argtypes = [ctypes.c_size_t]
    lib.sr_free.argtypes = [ctypes.c_void_p]
    lib.sr_pool_create.restype = ctypes.c_void_p
    lib.sr_pool_create.argtypes = []
    lib.sr_pool_destroy.argtypes = [ctypes.c_void_p]
    lib.sr_pool_get.restype = ctypes.c_void_p
    lib.sr_pool_get.argtypes = [ctypes.c_void_p, ctypes.c_size_t]
    lib.sr_pool_put.restype = ctypes.c_int
    lib.sr_pool_put.argtypes = [ctypes.c_void_p, ctypes.c_void_p]
    lib.sr_pool_class_of.restype = ctypes.c_size_t
    lib.sr_pool_class_of.argtypes = [ctypes.c_size_t]
    lib.sr_pool_stats.argtypes = [ctypes.c_void_p] + \
        [ctypes.POINTER(ctypes.c_long)] * 4
    lib.sr_write_file.restype = ctypes.c_long
    lib.sr_write_file.argtypes = [ctypes.c_char_p, ctypes.c_void_p,
                                  ctypes.c_size_t]
    lib.sr_read_file.restype = ctypes.c_long
    lib.sr_read_file.argtypes = [ctypes.c_char_p, ctypes.c_void_p,
                                 ctypes.c_size_t]
    lib.sr_file_size.restype = ctypes.c_long
    lib.sr_file_size.argtypes = [ctypes.c_char_p]
    lib.sr_spooler_create.restype = ctypes.c_void_p
    lib.sr_spooler_create.argtypes = [ctypes.c_size_t]
    lib.sr_spooler_submit.restype = ctypes.c_int
    lib.sr_spooler_submit.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                      ctypes.c_void_p, ctypes.c_size_t]
    lib.sr_spooler_drain.restype = ctypes.c_long
    lib.sr_spooler_drain.argtypes = [ctypes.c_void_p]
    lib.sr_spooler_destroy.argtypes = [ctypes.c_void_p]
    # serde codec entry points are newer than the pool/spool ABI: a
    # prebuilt library from an older source tree may lack them. Staging
    # still works without them — the serde layer just keeps its numpy
    # path (sr_has_codec gates dispatch). sr_codec_abi() returns 1 only
    # on little-endian hosts, where native rows match the '<u4' wire
    # format byte-for-byte.
    try:
        lib.sr_codec_abi.restype = ctypes.c_int
        lib.sr_codec_abi.argtypes = []
        lib.sr_encode_rows.restype = ctypes.c_long
        lib.sr_encode_rows.argtypes = [
            ctypes.c_void_p,   # objs  PyObject*[n] (numpy object array)
            ctypes.c_void_p,   # bytes_type  id(bytes)
            ctypes.c_int64,    # size_off  ob_size offset in bytes objects
            ctypes.c_int64,    # data_off  payload offset in bytes objects
            ctypes.c_void_p,   # keys  uint32[n * key_words]
            ctypes.c_int64,    # n
            ctypes.c_int64,    # key_words
            ctypes.c_int64,    # slot_words
            ctypes.c_int64,    # max_payload_bytes
            ctypes.c_void_p,   # out   uint32[n * row_words]
            ctypes.c_int64,    # threads
        ]
        lib.sr_decode_plan.restype = ctypes.c_long
        lib.sr_decode_plan.argtypes = [
            ctypes.c_void_p,   # rows  uint32[n * row_words]
            ctypes.c_int64,    # n
            ctypes.c_int64,    # key_words
            ctypes.c_int64,    # slot_words
            ctypes.c_int64,    # base  stream offset of the first item
            ctypes.c_void_p,   # soff  int64[n] out
        ]
        lib.sr_decode_rows.restype = ctypes.c_long
        lib.sr_decode_rows.argtypes = [
            ctypes.c_void_p,   # rows  uint32[n * row_words]
            ctypes.c_int64,    # n
            ctypes.c_int64,    # key_words
            ctypes.c_int64,    # slot_words
            ctypes.c_void_p,   # keys_out uint32[n * key_words]
            ctypes.c_void_p,   # soff  int64[n] pickle-stream row offsets
            ctypes.c_void_p,   # stream_out uint8[] pickle item stream
            ctypes.c_int64,    # threads
        ]
        lib.sr_has_codec = bool(lib.sr_codec_abi())
    except AttributeError:
        lib.sr_has_codec = False
    # columnar (v2) codec entry points are newer still — feature-detect
    # separately so a library with the v1 codec but not the columnar one
    # keeps v1 native while the columnar layer uses its numpy fallback
    try:
        lib.sr_encode_cols.restype = ctypes.c_long
        lib.sr_encode_cols.argtypes = [
            ctypes.c_void_p,   # keys  uint32[n * key_words]
            ctypes.c_int64,    # n
            ctypes.c_int64,    # key_words
            ctypes.c_int64,    # row_words
            ctypes.c_int64,    # ncols  fixed-width column count
            ctypes.c_void_p,   # srcs  void*[ncols] column storage
            ctypes.c_void_p,   # widths  int64[ncols] words per element
            ctypes.c_void_p,   # dst_off int64[ncols] payload word offset
            ctypes.c_int64,    # var_len_word (-1 = no varlen column)
            ctypes.c_int64,    # var_slot_words
            ctypes.c_int64,    # var_max_bytes
            ctypes.c_void_p,   # var_off int64[n + 1] heap offsets
            ctypes.c_void_p,   # var_heap uint8[]
            ctypes.c_void_p,   # out   uint32[n * row_words]
            ctypes.c_int64,    # threads
        ]
        lib.sr_decode_cols.restype = ctypes.c_long
        lib.sr_decode_cols.argtypes = [
            ctypes.c_void_p,   # rows  uint32[n * row_words]
            ctypes.c_int64,    # n
            ctypes.c_int64,    # key_words
            ctypes.c_int64,    # row_words
            ctypes.c_int64,    # ncols  fixed columns to gather
            ctypes.c_void_p,   # dsts  void*[ncols] contiguous outputs
            ctypes.c_void_p,   # widths  int64[ncols]
            ctypes.c_void_p,   # src_off int64[ncols]
            ctypes.c_int64,    # var_len_word (-1 = no varlen column)
            ctypes.c_int64,    # var_slot_words
            ctypes.c_void_p,   # var_off int64[n + 1] heap offsets
            ctypes.c_void_p,   # var_heap uint8[] out
            ctypes.c_int64,    # threads
        ]
        lib.sr_has_cols = bool(getattr(lib, "sr_has_codec", False))
    except AttributeError:
        lib.sr_has_cols = False
    return lib


def load_native(build_if_missing: bool = True) -> Optional[ctypes.CDLL]:
    """Load (building on demand) the staging library; None on failure.

    ``SPARKRDMA_NATIVE_FLAVOR=tsan|asan`` switches the whole process to
    the matching sanitizer-instrumented build
    (``libsparkstaging-<flavor>.so``, see ``native/Makefile``). One
    library per process — the flavor is read once on first load and the
    result cached like the plain path.
    """
    global _lib, _lib_attempted
    with _lib_lock:
        if _lib is not None or _lib_attempted:
            return _lib
        _lib_attempted = True
        flavor = native_flavor()
        lib_path = _flavored_lib_path(flavor)
        try:
            if build_if_missing:
                # make is incremental: a no-op when the library is
                # current, a rebuild when staging.cpp grew entry points
                # since the .so was produced (the serde codec did exactly
                # that). A failed make — no toolchain — still falls
                # through to loading whatever prebuilt library exists.
                try:
                    # _lib_lock exists precisely to serialize this
                    # one-shot build+dlopen: concurrent first callers
                    # must block until the single build finishes, and
                    # the result is cached so the lock is never held
                    # for the build again. Leaf lock by design.
                    # srlint: ignore[blocking-under-lock]
                    subprocess.run(
                        ["make", "-C", str(_NATIVE_DIR), flavor or "all"],
                        check=True, capture_output=True, timeout=120,
                    )
                except (OSError, subprocess.SubprocessError):
                    if not lib_path.exists():
                        raise
            if lib_path.exists():
                _lib = _declare(ctypes.CDLL(str(lib_path)))
                log.info("native staging library loaded: %s", lib_path)
        except (OSError, subprocess.SubprocessError) as e:
            log.warning("native staging unavailable (%s); numpy fallback", e)
            _lib = None
        return _lib


def codec_available() -> bool:
    """True when the native serde codec can be dispatched: library
    loaded, codec entry points present, little-endian host."""
    lib = load_native()
    return lib is not None and bool(getattr(lib, "sr_has_codec", False))


def cols_available() -> bool:
    """True when the columnar (v2) codec entry points can be dispatched
    — newer than the v1 codec ABI, feature-detected separately."""
    lib = load_native()
    return lib is not None and bool(getattr(lib, "sr_has_cols", False))


class HostBuffer:
    """One aligned host buffer (native) or numpy array (fallback)."""

    def __init__(self, nbytes: int, ptr: Optional[int],
                 pool: "HostBufferPool"):
        self.nbytes = nbytes
        self._ptr = ptr
        self._pool = pool
        self._released = False
        if ptr is None:  # fallback
            self._np = np.empty(nbytes, dtype=np.uint8)
        else:
            self._np = np.ctypeslib.as_array(
                (ctypes.c_uint8 * nbytes).from_address(ptr))

    def view(self, dtype=np.uint8, shape=None) -> np.ndarray:
        a = self._np.view(dtype)
        return a if shape is None else a[:int(np.prod(shape))].reshape(shape)

    @property
    def address(self) -> Optional[int]:
        return self._ptr

    def release(self) -> None:
        self._pool.put(self)


class HostBufferPool:
    """Size-classed aligned host buffer pool (RdmaBufferManager analogue)."""

    def __init__(self, use_native: bool = True):
        self._lib = load_native() if use_native else None
        self._handle = (self._lib.sr_pool_create()
                        if self._lib is not None else None)
        # fallback free stacks
        self._free: Dict[int, List[np.ndarray]] = {}
        self._fb_hits = 0
        self._fb_misses = 0
        self._lock = threading.Lock()

    @property
    def native(self) -> bool:
        return self._handle is not None

    @staticmethod
    def size_class(nbytes: int) -> int:
        c = 256
        while c < nbytes:
            c <<= 1
        return c

    def get(self, nbytes: int) -> HostBuffer:
        cls = self.size_class(nbytes)
        if self._handle is not None:
            ptr = self._lib.sr_pool_get(self._handle, cls)
            if not ptr:
                raise MemoryError(f"host pool allocation of {cls} B failed")
            return HostBuffer(cls, ptr, self)
        with self._lock:
            stack = self._free.get(cls)
            if stack:
                arr = stack.pop()
                self._fb_hits += 1
            else:
                arr = np.empty(cls, dtype=np.uint8)
                self._fb_misses += 1
        buf = HostBuffer.__new__(HostBuffer)
        buf.nbytes = cls
        buf._ptr = None
        buf._pool = self
        buf._np = arr
        buf._released = False
        return buf

    def put(self, buf: HostBuffer) -> None:
        if getattr(buf, "_released", False):
            raise ValueError("buffer already released")
        buf._released = True
        if self._handle is not None and buf._ptr is not None:
            rc = self._lib.sr_pool_put(self._handle,
                                       ctypes.c_void_p(buf._ptr))
            if rc != 0:
                raise ValueError("buffer not owned by pool (double release?)")
            buf._ptr = None
            return
        with self._lock:
            self._free.setdefault(buf.nbytes, []).append(buf._np)

    def stats(self) -> Dict[str, int]:
        if self._handle is not None:
            vals = [ctypes.c_long() for _ in range(4)]
            self._lib.sr_pool_stats(self._handle, *[ctypes.byref(v)
                                                    for v in vals])
            return {"hits": vals[0].value, "misses": vals[1].value,
                    "outstanding": vals[2].value,
                    "bytes_allocated": vals[3].value, "native": 1}
        with self._lock:
            return {"hits": self._fb_hits, "misses": self._fb_misses,
                    "outstanding": -1, "bytes_allocated": -1, "native": 0}

    def close(self) -> None:
        if self._handle is not None:
            self._lib.sr_pool_destroy(self._handle)
            self._handle = None
        self._free.clear()


def _fire_spill_write(path: str) -> bool:
    """Consult the fault plane at ``spill.write``; True = corrupt payload.

    An injected transient write failure is retried once in place
    (counted as a ``spill_rewrite`` recovery — the transient-IO
    hardening rung); a persistent one raises the writer's OSError
    contract instead of looping.
    """
    from sparkrdma_tpu import faults as _faults

    act = _faults.fire("spill.write")
    if act == "fail":
        act = _faults.fire("spill.write")   # one bounded in-place retry
        if act == "fail":
            raise OSError(
                f"injected fault (spill.write): write of {path} failed "
                "twice — giving up")
        _faults.note_recovery("spill_rewrite")
    return act == "corrupt"


class SpillWriter:
    """Pipelined spill-to-disk: submit arrays, keep computing, drain once.

    Native path: a C++ writer thread with a bounded queue writes each
    buffer while the caller proceeds (submissions hold a reference to the
    source array so its memory stays alive until drain). Fallback: the
    same contract via a Python thread.
    """

    def __init__(self, depth: int = 8, use_native: bool = True,
                 codec: str = "", level: int = 1, checksum: bool = True,
                 pool: Optional["HostBufferPool"] = None):
        # codec != "": every submitted array is compressed (header +
        # blob, see compress_array). Compression runs synchronously in
        # submit() — zlib releases the GIL but the caller still waits;
        # it is an opt-in trade of submit latency for disk bytes.
        #
        # pool: stage CRC frames in HostBufferPool leases instead of
        # fresh np.concatenate allocations (released at drain/close) —
        # steady-state spilling stops allocating.
        if codec and codec not in _CODEC_IDS:
            raise ValueError(f"unknown compression codec {codec!r}")
        self._codec = codec
        self._level = level
        self._checksum = checksum
        self._pool = pool
        self._leases: List[HostBuffer] = []   # released at drain/close
        self._lib = load_native() if use_native else None
        self._pending: List[np.ndarray] = []  # keep-alive until drain
        if self._lib is not None:
            self._handle = self._lib.sr_spooler_create(depth)
            self._fb = None
        else:
            self._handle = None
            import queue as _q

            self._fb_q: "_q.Queue" = _q.Queue(maxsize=depth)
            self._fb_lock = threading.Lock()
            self._fb_errors = 0                # guarded-by: _fb_lock
            self._fb_stop = False              # guarded-by: _fb_lock
            self._fb = threading.Thread(target=self._fb_loop, daemon=True)
            self._fb.start()

    def _fb_loop(self) -> None:
        import queue as _q
        while True:
            try:
                # bounded wait so a lost sentinel (e.g. an interpreter
                # tearing down mid-close) cannot park this thread
                # forever; the stop flag is the durable exit signal
                item = self._fb_q.get(timeout=1.0)
            except _q.Empty:
                with self._fb_lock:
                    if self._fb_stop:
                        return
                continue
            if item is None:
                self._fb_q.task_done()
                return
            path, arr = item
            try:
                arr.tofile(path)
            except OSError:
                with self._fb_lock:
                    self._fb_errors += 1
            self._fb_q.task_done()

    def submit(self, path: str, arr: np.ndarray) -> None:
        _count_spill(arr.nbytes)
        corrupt = _fire_spill_write(path)
        if self._codec:
            arr = np.frombuffer(
                compress_array(arr, self._codec, self._level), np.uint8)
        if self._checksum:
            if self._pool is not None:
                arr, lease = crc_frame_into(arr, self._pool)
                self._leases.append(lease)
            else:
                arr = crc_frame(arr)
            if corrupt:
                # storage-corruption injection: the trailer holds the
                # TRUE payload's CRC, the payload is mangled — exactly
                # what a bit flip after the write would look like
                arr[0] ^= 0x01
        arr = np.ascontiguousarray(arr)
        self._pending.append(arr)  # keep alive
        if self._handle is not None:
            rc = self._lib.sr_spooler_submit(
                self._handle, path.encode(), arr.ctypes.data, arr.nbytes)
            if rc != 0:
                raise RuntimeError("spooler stopped")
        else:
            self._fb_q.put((path, arr))

    def drain(self) -> int:
        """Block until all writes land; return THIS batch's error count.

        The counter resets on drain (both native and fallback paths), so
        a long-lived writer reused after one failed batch does not keep
        reporting stale errors."""
        if self._handle is not None:
            errors = int(self._lib.sr_spooler_drain(self._handle))
        else:
            self._fb_q.join()
            with self._fb_lock:
                errors = self._fb_errors
                self._fb_errors = 0
        self._pending.clear()
        self._release_leases()
        return errors

    def _release_leases(self) -> None:
        for lease in self._leases:
            lease.release()
        self._leases.clear()

    def close(self) -> None:
        if self._handle is not None:
            self._lib.sr_spooler_drain(self._handle)
            self._lib.sr_spooler_destroy(self._handle)
            self._handle = None
        elif self._fb is not None:
            with self._fb_lock:
                self._fb_stop = True
            self._fb_q.put(None)
            self._fb.join(timeout=10)
            self._fb = None
        self._pending.clear()
        self._release_leases()


def write_array(path: str, arr: np.ndarray, use_native: bool = True,
                codec: str = "", level: int = 1,
                checksum: bool = True,
                pool: Optional[HostBufferPool] = None) -> None:
    """Synchronous single-array spill (optionally compressed), ending in
    a CRC32 trailer (``checksum=False`` reproduces the legacy layout).
    ``pool`` stages the CRC frame in a pooled lease (released before
    return) so repeated spills stop allocating."""
    _count_spill(arr.nbytes)
    corrupt = _fire_spill_write(path)
    if codec:
        arr = np.frombuffer(compress_array(arr, codec, level), np.uint8)
    lease = None
    if checksum:
        if pool is not None:
            arr, lease = crc_frame_into(arr, pool)
        else:
            arr = crc_frame(arr)
        if corrupt:
            arr[0] ^= 0x01   # see SpillWriter.submit
    try:
        arr = np.ascontiguousarray(arr)
        lib = load_native() if use_native else None
        if lib is not None:
            rc = lib.sr_write_file(path.encode(), arr.ctypes.data,
                                   arr.nbytes)
            if rc != arr.nbytes:
                raise OSError(f"native write to {path} failed: rc={rc}")
        else:
            arr.tofile(path)
    finally:
        if lease is not None:
            lease.release()


def read_array(path: str, dtype, shape, use_native: bool = True,
               out: Optional[np.ndarray] = None) -> np.ndarray:
    """Read back a spilled array of known dtype/shape.

    Compressed files self-describe (header leads with the codec magic
    and declares the raw byte count), so the same call reads both raw
    rounds-1-4 files and round-5 compressed ones. Detection is
    header-first: a compressed file is recognized even when its total
    size coincides with the raw layout's (the size-only test would
    silently hand back compressed bytes as records), and a raw file
    that merely STARTS with the magic falls through to the raw path
    via the header's raw-size field disagreeing.

    ``out``: a C-contiguous destination of exactly ``shape``/``dtype``
    (e.g. a :class:`HostBufferPool` lease view) — the payload lands
    there and ``out`` is returned, so fetch loops stop allocating.
    """
    from sparkrdma_tpu import faults as _faults

    tsz = _CRC_TRAILER.size
    expected = int(np.prod(shape)) * np.dtype(dtype).itemsize
    act = _faults.fire("spill.read")
    if act == "fail":
        raise OSError(f"injected fault (spill.read): {path}")
    corrupt = act == "corrupt"
    try:
        actual = os.path.getsize(path)
    except OSError as e:
        raise OSError(f"spill file {path} unreadable: {e}") from e
    if actual >= _HDR.size:
        with open(path, "rb") as f:
            head = f.read(_HDR.size)
            magic, cid, raw_n = _HDR.unpack(head)
            if (magic == _CODEC_MAGIC and cid in _CODEC_IDS.values()
                    and raw_n == expected):
                data = head + f.read()
                if (len(data) >= _HDR.size + tsz
                        and data[-tsz:-tsz + 4] == _CRC_MAGIC):
                    body = data[:-tsz]
                    if corrupt:
                        body = _faults.mangle(body)
                    verify_crc(np.frombuffer(body, np.uint8),
                               data[-tsz:], path)
                    data = body
                raw = decompress_blob(data)
                if len(raw) != expected:
                    raise OSError(f"spill file {path} holds {len(raw)} "
                                  f"raw bytes, expected {expected}")
                decoded = np.frombuffer(raw, dtype=dtype).reshape(shape)
                if out is not None:
                    out[...] = decoded
                    return out
                return decoded.copy()
    has_trailer = actual == expected + tsz
    if actual != expected and not has_trailer:
        raise OSError(f"spill file {path} is {actual} bytes, expected "
                      f"{expected} raw (and no valid compression "
                      "header) — truncated or corrupt")
    if out is None:
        out = np.empty(shape, dtype=dtype)
    lib = load_native() if use_native else None
    if lib is not None:
        # reads the first out.nbytes bytes — the trailer, when present,
        # is fetched separately below
        rc = lib.sr_read_file(path.encode(), out.ctypes.data, out.nbytes)
        if rc != out.nbytes:
            raise OSError(f"native read of {path} short: rc={rc}")
    else:
        with open(path, "rb") as f:
            n = f.readinto(memoryview(_as_u8(out))[:expected])
        if n != expected:
            raise OSError(f"spill file {path} has wrong size")
    if has_trailer:
        with open(path, "rb") as f:
            f.seek(expected)
            trailer = f.read(tsz)
        if corrupt:
            _as_u8(out)[0] ^= 0x01
        verify_crc(out, trailer, path)
    return out


__all__ = ["HostBufferPool", "HostBuffer", "SpillWriter", "write_array",
           "read_array", "load_native", "codec_available",
           "compress_array", "decompress_blob", "spill_count",
           "crc_frame", "crc_frame_into", "verify_crc"]
