"""Pinned staging pools + DeviceBuf payload handles — the L0 layer.

The reference avoids payload copies with bufferlist: a message's data
segment is received into page-aligned buffers once and every later
consumer (crc, EC encode, BlueStore) reads the SAME memory.  Our
equivalent for a device-offloaded OSD: client write payloads land in a
**pinned staging pool** (preallocated, bounded — the h2d DMA source on
a real TPU rig) and ride to the device per *coalesced batch* (the
StripeBatchQueue upload).  That is the design, not yet the measured
path: the queue uploads a batch twice as numpy (its data planes for the
encode, data + coding planes again for the crc pass: 1 and 1.5 MiB a
1 MiB job) and fetches the coding planes whole in between, so more
than metadata crosses back (PERF §5; ROADMAP S3).  A ``DeviceBuf`` is the
payload's handle through the whole pipeline: messenger dispatch ->
``ObjectState.data`` -> ``ECBackend.submit`` -> ``Transaction`` ->
store apply / wire serialization.

Buffer-ownership rules (who may materialize host bytes, and how it is
accounted — enforced by the ``no-d2h-on-hot-path`` cephlint check and
measured by ``DevPathStats``):

- ``stage()``            the ONE receive-side copy (socket frame ->
                         pinned slot); not a crossing, it IS the
                         staging the pool exists for.
- queue batch build      the ONE h2d upload, counted in ``h2d_bytes``
                         per coalesced batch (``staged_batches``).
- ``wire_view()``        sanctioned sinks (store apply, messenger
                         frame): zero-copy while the payload is still
                         host-staged; counted in ``d2h_bytes`` once
                         the handle's truth has moved to the device
                         (post-seal data planes, device-born parity).
- ``tobytes()``/slicing  UNSANCTIONED on the write hot path: every
                         call counts ``payload_host_touches``.  The
                         happy EC WRITEFULL path must keep this at 0
                         — tests/test_device_datapath.py asserts it.

Tier-1 runs ``JAX_PLATFORMS=cpu``, where "device" arrays share host
RAM — so the copy-count/bytes-crossed COUNTERS are the CI-provable
invariant; speeds are the benchmark's to measure (PERF.md).
"""

from __future__ import annotations

import os
import threading
from typing import Dict, Optional

import numpy as np

from ceph_tpu.core.lockdep import make_lock

# staging pool geometry (overridable via conf tpu_staging_* / env
# CEPH_TPU_TPU_STAGING_*); one pool serves the whole process — it is
# owned by the default StripeBatchQueue, like the reference's msgr
# buffer pools are owned by the transport
DEFAULT_SLOT_BYTES = 128 << 10
DEFAULT_SLOTS = 64


def devpath_enabled(conf=None) -> bool:
    """Device-resident small-object data path kill switch."""
    if conf is not None:
        try:
            return bool(conf.get("tpu_devpath"))
        except KeyError:  # pre-schema Config stub (unit tests)
            pass
    return os.environ.get("CEPH_TPU_TPU_DEVPATH", "1") not in (
        "0", "false", "no", "off")


class DevPathStats:
    """d2h/h2d accounting: "metadata-only host crossing" as a measured
    invariant, not a claim.  Registered per daemon as ``osd.N.tpu``."""

    def __init__(self) -> None:
        self._lock = make_lock("staging.stats")
        self.h2d_bytes = 0           # payload bytes uploaded (batch build)
        self.d2h_bytes = 0           # payload bytes fetched back to host
        self.staged_batches = 0      # coalesced device batches uploaded
        self.payload_host_touches = 0  # unsanctioned host materializations
        self.pool_occupancy_hw = 0   # staging slots in use, high-water

    def inc(self, name: str, by: int = 1) -> None:
        with self._lock:
            setattr(self, name, getattr(self, name) + by)

    def note_occupancy(self, occ: int) -> None:
        with self._lock:
            if occ > self.pool_occupancy_hw:
                self.pool_occupancy_hw = occ

    def snapshot(self) -> Dict[str, int]:
        with self._lock:
            return {
                "h2d_bytes": self.h2d_bytes,
                "d2h_bytes": self.d2h_bytes,
                "staged_batches": self.staged_batches,
                "payload_host_touches": self.payload_host_touches,
                "pool_occupancy_hw": self.pool_occupancy_hw,
            }

    def perf_view(self, name: str):
        """A PerfCounters-compatible read-only view for
        ``ctx.perf.register(f"osd.N.tpu", ...)`` — dumps live from the
        process-wide stats (the pool, like the queue, is shared by
        every in-process daemon)."""
        stats = self

        class _View:
            def __init__(self) -> None:
                self.name = name

            def dump(self) -> Dict[str, int]:
                return stats.snapshot()

        return _View()


class StagingSlot:
    """One pinned region: a view into the pool's preallocated slab
    (or a dedicated oversize buffer for payloads beyond slot_bytes)."""

    __slots__ = ("index", "arr", "nbytes")

    def __init__(self, index: int, arr: np.ndarray, nbytes: int) -> None:
        self.index = index      # -1 = oversize (not pool-backed)
        self.arr = arr          # uint8 view, len == nbytes
        self.nbytes = nbytes


class StagingPool:
    """Bounded pinned staging: ``acquire`` BLOCKS when every slot is in
    use (backpressure to the op path — never drops, never deadlocks:
    slots release on the fan-out/commit side, which does not wait on
    admission), and ``pool_occupancy_hw`` records the pressure."""

    def __init__(self, slot_bytes: Optional[int] = None,
                 slots: Optional[int] = None,
                 stats: Optional[DevPathStats] = None) -> None:
        # geometry from env (the same CEPH_TPU_TPU_STAGING_* variables
        # the Config schema reads) — the process-wide pool is built
        # before any daemon Context exists
        if slot_bytes is None:
            slot_bytes = int(os.environ.get(
                "CEPH_TPU_TPU_STAGING_SLOT_KIB", DEFAULT_SLOT_BYTES >> 10
            )) << 10
        if slots is None:
            slots = int(os.environ.get(
                "CEPH_TPU_TPU_STAGING_SLOTS", DEFAULT_SLOTS))
        self.slot_bytes = slot_bytes
        self.nslots = slots
        self.stats = stats or DevPathStats()
        # one slab, sliced into slots: the real-rig analog is a single
        # pinned (page-locked) allocation registered for DMA once
        self._slab = np.zeros(slot_bytes * slots, dtype=np.uint8)
        self._free = list(range(slots - 1, -1, -1))
        self._cond = threading.Condition(make_lock("staging.pool"))

    @property
    def occupancy(self) -> int:
        with self._cond:
            return self.nslots - len(self._free)

    def configure(self, slot_bytes: int, slots: int) -> bool:
        """Resize an IDLE pool (conf plumbing: the process-wide pool is
        built before any daemon Context exists, so daemons apply their
        tpu_staging_* conf here at init).  Returns False — and changes
        nothing — while any slot is in use."""
        with self._cond:
            if self.nslots - len(self._free) > 0:
                return False
            if (slot_bytes, slots) == (self.slot_bytes, self.nslots):
                return True
            self.slot_bytes = slot_bytes
            self.nslots = slots
            self._slab = np.zeros(slot_bytes * slots, dtype=np.uint8)
            self._free = list(range(slots - 1, -1, -1))
            return True

    def acquire(self, nbytes: int,
                timeout: Optional[float] = None) -> Optional[StagingSlot]:
        """A slot holding ``nbytes``; blocks while the pool is
        exhausted.  ``timeout`` None = wait forever; on timeout returns
        None and the caller falls back to the host path (degrade, don't
        wedge).  Payloads larger than a slot get a dedicated buffer —
        big writes are rare on the small-object path and must not
        starve it of slots."""
        if nbytes > self.slot_bytes:
            return StagingSlot(-1, np.empty(nbytes, dtype=np.uint8), nbytes)
        with self._cond:
            if not self._free and not self._cond.wait_for(
                    lambda: bool(self._free), timeout=timeout):
                return None
            idx = self._free.pop()
            self.stats.note_occupancy(self.nslots - len(self._free))
        base = idx * self.slot_bytes
        return StagingSlot(idx, self._slab[base:base + nbytes], nbytes)

    def release(self, slot: StagingSlot) -> None:
        if slot.index < 0:
            return  # oversize: plain GC
        with self._cond:
            self._free.append(slot.index)
            self._cond.notify()


class DeviceBuf:
    """Payload handle that flows bufferlist-style through the write
    pipeline without materializing intermediate ``bytes`` copies.

    Lifecycle: ``stage()`` binds it to a staging slot (host, pinned);
    the backend attaches the interleaved data planes at submit; after
    fan-out both the local store apply and the wire frames have read
    the staged memory, ``seal()`` returns the slot to the pool and the
    handle's truth becomes the device-resident planes (late readers —
    the projected-state cache, degraded re-reads — fetch from the
    device, counted).  ``wrap_device()`` makes handles for device-born
    payloads (parity planes out of the encode batch)."""

    __slots__ = ("_kind", "_arr", "_planes", "_size", "_k", "_unit",
                 "_slot", "_pool", "_stats", "_lock")

    def __init__(self, kind: str, arr: Optional[np.ndarray],
                 stats: DevPathStats,
                 slot: Optional[StagingSlot] = None,
                 pool: Optional[StagingPool] = None) -> None:
        self._kind = kind          # "host" | "planes" | "dev" | "bytes"
        self._arr = arr            # host/dev: uint8 [n]; bytes: bytes
        self._planes = None        # post-seal [k, cols] device planes
        self._size = len(arr) if arr is not None else 0
        self._k = 0
        self._unit = 0
        self._slot = slot
        self._pool = pool
        self._stats = stats
        # seal() (fan-out thread) races late readers (projected-state
        # cache fetches on op threads): state transitions and reads
        # serialize here
        self._lock = make_lock("staging.devbuf")

    # -- constructors -----------------------------------------------------
    @classmethod
    def stage(cls, pool: StagingPool, data,
              timeout: Optional[float] = 30.0) -> Optional["DeviceBuf"]:
        """The receive-side copy: frame payload -> pinned slot.  Returns
        None when the pool stays exhausted past ``timeout`` (callers
        keep the plain-bytes host path; backpressure, not failure)."""
        src = np.frombuffer(data, dtype=np.uint8)
        slot = pool.acquire(src.size, timeout=timeout)
        if slot is None:
            return None
        np.copyto(slot.arr, src)
        return cls("host", slot.arr, pool.stats, slot=slot, pool=pool)

    @classmethod
    def wrap_device(cls, arr: np.ndarray,
                    stats: DevPathStats) -> "DeviceBuf":
        """Device-born payload (encode output parity plane)."""
        a = np.ascontiguousarray(arr).reshape(-1)
        return cls("dev", a, stats)

    @classmethod
    def wrap_host(cls, arr: np.ndarray, stats: DevPathStats) -> "DeviceBuf":
        """Host-pinned payload view (a staged data plane row): sinks
        read it zero-copy, nothing crosses."""
        a = arr if arr.ndim == 1 else arr.reshape(-1)
        return cls("host", a, stats)

    # -- sizing -----------------------------------------------------------
    def __len__(self) -> int:
        return self._size

    @property
    def nbytes(self) -> int:
        return self._size

    # -- pipeline hooks ---------------------------------------------------
    def np1d(self) -> np.ndarray:
        """Staged uint8 view for the interleave/encode input build —
        part of the single sanctioned upload path, not a crossing
        while host-staged.  A SEALED handle (a projected state being
        re-submitted by a same-object successor op) fetches from its
        device planes — counted, though on a real rig this re-encode
        input would stay device-to-device."""
        with self._lock:
            if self._kind == "host":
                return self._arr
            if self._kind == "bytes":
                return np.frombuffer(self._arr, dtype=np.uint8)
            if self._kind == "dev":
                return self._arr
            self._stats.inc("d2h_bytes", self._size)
            return self._deinterleave()

    def attach_planes(self, planes: np.ndarray, k: int, unit: int) -> None:
        """Bind the interleaved data planes this payload became; after
        seal() they are the handle's (device-resident) truth."""
        with self._lock:
            self._planes = planes
            self._k = k
            self._unit = unit

    def seal(self) -> None:
        """Fan-out done: every host sink (store, wire) has read the
        staged slot — return it to the pool.  With planes attached the
        handle stays alive device-side; without (early bail), keep a
        host copy so late readers still see the bytes."""
        from ceph_tpu.core import failpoint as fp

        if fp.enabled("staging.seal"):
            fp.failpoint("staging.seal", size=self._size)
        with self._lock:
            if self._slot is not None:
                if self._planes is not None:
                    self._arr = None
                    self._kind = "planes"
                else:
                    self._arr = bytes(self._slot.arr)
                    self._kind = "bytes"
                self._pool.release(self._slot)
                self._slot = None
            elif self._planes is not None and self._kind != "planes":
                self._arr = None
                self._kind = "planes"

    def discard(self) -> None:
        """Early-bail release (op answered without executing): return
        the slot WITHOUT seal()'s defensive host copy — nothing will
        read this payload again, the message is being dropped.  A
        stray late read sees an empty buffer, not freed memory."""
        with self._lock:
            if self._slot is not None:
                self._pool.release(self._slot)
                self._slot = None
            if self._planes is None and self._kind == "host":
                self._arr = b""
                self._kind = "bytes"
                self._size = 0

    # -- sinks ------------------------------------------------------------
    def _device_side(self) -> bool:
        return self._kind in ("planes", "dev")

    def _deinterleave(self) -> np.ndarray:
        p = self._planes
        S = p.shape[1] // self._unit if self._unit else 0
        flat = p[:, :S * self._unit].reshape(
            self._k, S, self._unit).transpose(1, 0, 2).reshape(-1)
        return flat[:self._size]

    def _host_arr(self) -> np.ndarray:
        if self._kind == "planes":
            return self._deinterleave()
        return self.np1d()

    def wire_view(self):
        """Sanctioned materialization at a sink boundary (store apply,
        messenger frame).  Zero-copy while host-staged; a d2h fetch —
        counted — once the payload lives on the device."""
        with self._lock:
            if self._device_side():
                self._stats.inc("d2h_bytes", self._size)
            a = self._host_arr()
            return a if a.base is None else memoryview(a)

    def tobytes(self) -> bytes:
        """Unsanctioned host materialization: the thing the pipeline
        exists to eliminate.  Every call is a payload_host_touch."""
        self._stats.inc("payload_host_touches")
        with self._lock:
            if self._device_side():
                self._stats.inc("d2h_bytes", self._size)
            if self._kind == "bytes":
                return self._arr
            return self._host_arr().tobytes()

    def __bytes__(self) -> bytes:
        return self.tobytes()

    def __getitem__(self, key) -> bytes:
        """Read-path slicing (obc projected-state reads): a d2h fetch
        when device-side, but not a hot-path touch — reads are allowed
        to fetch what they return to the client."""
        if isinstance(key, slice):
            with self._lock:
                a = self._host_arr()
                if self._device_side():
                    sub = a[key]
                    self._stats.inc("d2h_bytes", int(sub.size))
                    return sub.tobytes()
                if self._kind == "bytes":
                    return self._arr[key]
                return a[key].tobytes()
        raise TypeError("DeviceBuf supports slice reads only")

    def __del__(self) -> None:
        # safety net: a handle dropped without seal() (crashed op path)
        # must not leak its pinned slot forever.  No other refs exist
        # at GC time, so no lock is needed.
        slot = getattr(self, "_slot", None)
        pool = getattr(self, "_pool", None)
        if slot is not None and pool is not None:
            self._slot = None
            pool.release(slot)

    def __repr__(self) -> str:
        return (f"DeviceBuf({self._kind}, {self._size}B"
                f"{', slot' if self._slot is not None else ''})")
