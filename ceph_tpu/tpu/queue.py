"""StripeBatchQueue — coalesce concurrent EC encodes into wide matmuls.

The write path hands each object's [k, chunk] data planes to this
queue and blocks on a future; a worker thread greedily drains jobs
that share a codec, concatenates them along the column axis, runs ONE
device matmul, and splits the coding planes back out.  Dispatch cost
is amortized over every write in flight — the TPU equivalent of the
reference's per-call SIMD batch (and the only way small stripes win;
see SURVEY.md §7 hard parts #2).

Nothing overlaps the device today: the one worker coalesces, stacks,
dispatches and then blocks in the fetch of batch N before it takes
batch N+1 (PERF §5: 55 ms of `dev_wait_ms.write` and 34 ms of host work,
one after the other, in an 89.5 ms cycle).  A worker that assembles
N+1 while the device runs N is ROADMAP D5.
"""

from __future__ import annotations

import itertools
import queue
import threading
import time
from concurrent.futures import Future
from typing import Dict, List, Tuple

import numpy as np

from ceph_tpu.core import tracing
from ceph_tpu.core.perf import PerfCounters
from ceph_tpu.tpu import devwatch, shapebucket
from ceph_tpu.tpu.staging import DevPathStats, StagingPool


class _Job:
    __slots__ = ("codec", "planes", "future", "kind", "sig", "size",
                 "t_enq", "trop", "chunk")

    def __init__(self, codec, planes: np.ndarray, kind: str = "enc",
                 sig: Tuple[int, ...] = (), size: int = 0,
                 trop=None, chunk: int = 0) -> None:
        self.codec = codec
        self.planes = planes
        # array codecs (clay): the bytes of ONE codeword's chunk.  A row
        # of `planes` is whole codewords one after another (a shard's
        # stripes, each coded by itself as upstream's ECUtil::encode
        # codes stripe_width bytes a call); 0 = the row is one codeword
        self.chunk = chunk
        # "enc" | "encp" (fused crc) | "dec" (flat recovery matmul) |
        # "cdec" (array-codec decode) | "crep" (clay sub-chunk repair)
        self.kind = kind
        self.sig = sig        # dec/cdec: survivor ids; crep: (lost, *helpers)
        self.size = size or planes.nbytes  # real payload bytes (h2d
        # accounting: stripe-tail zeros are device-side fill, not
        # transferred bytes)
        self.t_enq = time.monotonic()  # queue-wait attribution
        # the client op riding this job (TrackedOp), for op-level
        # compile blame: a batch whose wait window overlapped a live
        # XLA compile annotates the op with compile_wait
        self.trop = trop
        self.future: Future = Future()


class StripeBatchQueue:
    def __init__(
        self,
        max_batch_cols: int = 1 << 20,
        window_s: float = 0.0005,
        mesh=None,
    ) -> None:
        self.max_batch_cols = max_batch_cols
        self.window_s = window_s
        # optional MeshCompute (ceph_tpu.tpu.meshio): coalesced batches
        # with a plain coding matrix run data-parallel over the mesh's
        # stripe axis instead of on one device
        self.mesh = mesh
        self.mesh_batches = 0
        self._q: "queue.Queue[_Job | None]" = queue.Queue()
        self._thread = threading.Thread(
            target=self._worker, name="stripe-batch", daemon=True
        )
        self._started = False
        self._lock = threading.Lock()
        self.batches = 0       # perf: device dispatches
        # this queue in the span ring: `batches` is the `seq` of its
        # spans, and a process that made a second queue counts again
        self._span_q = tracing.recorder().next_id()
        self.jobs = 0          # perf: logical encodes
        self.bytes_in = 0      # perf: plane bytes that rode the queue
        # jobs-per-batch histogram {width: batches}: the direct
        # evidence of whether concurrent writes actually coalesced
        # (mean width 1.0 == the pipeline fed the queue one job at a
        # time and the batching engine idled)
        self.batch_jobs: Dict[int, int] = {}
        # decode-only slice of the same evidence: recovery windows and
        # concurrent degraded reads sharing a survivor signature
        # should show widths > 1 here
        self.dec_batch_jobs: Dict[int, int] = {}
        # device-resident data path: the queue owns the pinned staging
        # pool (payloads land here at messenger dispatch and ride to
        # the device once per coalesced batch) and the d2h/h2d
        # accounting that makes "metadata-only host crossing" a
        # measured invariant (registered per daemon as osd.N.tpu)
        self.stats = DevPathStats()
        self.pool = StagingPool(stats=self.stats)
        # stage-latency attribution (PR 8): where an encode's time
        # goes — waiting in this queue (coalescing window included) vs
        # the device matmul(+crc) vs handing results back to the
        # futures.  Process-wide like the queue; each daemon registers
        # it in its context as osd.N.tpuq
        self.perf = PerfCounters("tpu.queue")
        self.perf.add_histogram(
            "lat_encq_wait_us", "job enqueue -> batch start (us)")
        self.perf.add_histogram(
            "lat_device_us",
            "host clock around one batch: stack, dispatch, fetch, crc "
            "layout, up to the fan-out; NOT device time (us)")
        self.perf.add_histogram(
            "lat_encq_dispatch_us",
            "batch result fan-out to futures (us)")
        # device-visibility gauges (the "as fast as the hardware
        # allows" dashboard numbers): sampled by the owning daemon's
        # stats tick via sample() into the same snapshot-ring
        # machinery the mon PGMap uses for cluster rates
        self.perf.add_u64_gauge(
            "queue_depth", "jobs waiting in the stripe batch queue")
        self.perf.add_u64_gauge(
            "device_busy_pct",
            "share of the sample window a host thread spent blocked "
            "on the device (dev.wait spans) (%)")
        self.perf.add_u64_gauge(
            "staging_slots_used", "pinned staging pool slots in use")
        # cumulative host clock around the batches (what lat_device_us
        # takes in): NOT device time, at saturation it grows a second
        # a second whatever the device does
        self.device_time_s = 0.0
        from ceph_tpu.core.perf import SnapshotRing

        self._gauge_ring = SnapshotRing(capacity=32)
        # the batch the device worker is executing RIGHT NOW (kind,
        # jobs, shapes, start stamp) — the crash flight recorder's
        # "what was the device doing when we died" evidence; None when
        # the worker is idle/coalescing
        self._inflight_info: "Dict | None" = None

    def inflight_batch(self) -> "Dict | None":
        """Snapshot of the batch currently on the device worker (for
        CrashArchive's device section); None when idle."""
        info = self._inflight_info
        if info is None:
            return None
        out = dict(info)
        out["age_s"] = round(time.monotonic() - out.pop("t0"), 3)
        return out

    def sample(self, window_s: float = 10.0) -> None:
        """Refresh the device-visibility gauges: called off the data
        path (the OSD stats tick) so `perf dump` and the
        Prometheus export show live queue depth, staging occupancy,
        and the device-busy fraction: the rate at which devwatch's
        cumulative `dev.wait` seconds grew over the ring window."""
        self._gauge_ring.push({"device_s": devwatch.watch().wait_s})
        busy = self._gauge_ring.rate("device_s", window_s)
        self.perf.set("device_busy_pct", int(round(min(1.0, busy) * 100)))
        self.perf.set("queue_depth", self._q.qsize())
        self.perf.set("staging_slots_used", self.pool.occupancy)

    def start(self) -> None:
        with self._lock:
            if not self._started:
                self._started = True
                if not self._thread.is_alive():
                    self._thread = threading.Thread(
                        target=self._worker, name="stripe-batch",
                        daemon=True)
                self._thread.start()

    def stop(self) -> None:
        if self._started:
            self._q.put(None)
            self._thread.join(timeout=10)
            self._started = False

    # -- API --------------------------------------------------------------
    def encode_async(self, codec, planes: np.ndarray,
                     trop=None, chunk: int = 0) -> Future:
        """planes: uint8 [k, n] -> Future of coding planes [m, n].
        `chunk` (here and below) matters to array codecs alone: a row
        is n/chunk codewords, each coded by itself (see _Job.chunk)."""
        self.start()
        job = _Job(codec, np.ascontiguousarray(planes, dtype=np.uint8),
                   trop=trop, chunk=chunk)
        self._q.put(job)
        return job.future

    def encode(self, codec, planes: np.ndarray,
               chunk: int = 0) -> np.ndarray:
        return self.encode_async(codec, planes, chunk=chunk).result()

    def encode_crc_async(self, codec, planes: np.ndarray,
                         size: int = 0, trop=None,
                         chunk: int = 0) -> Future:
        """Fused encode + per-shard crc32c: planes uint8 [k, n] ->
        Future of (coding [m, n], crcs u32 [k+m]).

        The device-resident write path: coding planes come out of the
        same coalesced matmul batch as encode_async, and every shard's
        HashInfo crc is computed ON the device in that batch — only
        the 4-byte digests cross back to host, so hinfo checksums stop
        forcing a d2h fetch (or host re-read) of payload bytes."""
        self.start()
        job = _Job(codec, np.ascontiguousarray(planes, dtype=np.uint8),
                   kind="encp", size=size, trop=trop, chunk=chunk)
        self._q.put(job)
        return job.future

    def decode_data_async(self, codec,
                          available: "Dict[int, np.ndarray]",
                          trop=None) -> Future:
        """Survivor planes {shard: [n]} -> Future of data planes [k, n].

        The decode twin of encode_async: jobs sharing a survivor
        SIGNATURE coalesce into one wide recovery matmul (the
        reference's per-signature cached decode matrix, ECBackend
        minimum_to_decode -> decode_chunks, batched the TPU way).
        Requires a flat matrix codec (recovery_matrix)."""
        self.start()
        sig = tuple(sorted(available))[: codec.k]
        stacked = np.ascontiguousarray(
            np.stack([np.asarray(available[i], dtype=np.uint8)
                      for i in sig]))
        job = _Job(codec, stacked, kind="dec", sig=sig, trop=trop)
        self._q.put(job)
        return job.future

    def decode_data(self, codec, available) -> np.ndarray:
        return self.decode_data_async(codec, available).result()

    def clay_repair_async(self, codec, lost: int, helpers,
                          planes: np.ndarray, trop=None,
                          chunk: int = 0) -> Future:
        """Layers-only survivor planes [d, L, s] (or, rows of n/chunk
        codewords, [d, S*L*s]: each stripe's L repair sub-chunks, stripe
        after stripe, as a helper reads them off its shard) -> Future of
        the rebuilt chunk bytes [S*Z*s] (row order = sorted helpers,
        layer order = codec.repair_layers(lost)).

        The MSR-repair twin of encode_async: concurrent single-shard
        repairs of the SAME lost shard (a recovery window draining one
        dead OSD is exactly this) coalesce along the intra-sub-chunk
        byte axis into one set of coupled-layer matmuls."""
        self.start()
        planes = np.ascontiguousarray(planes, dtype=np.uint8)
        job = _Job(codec, planes.reshape(planes.shape[0], -1),
                   kind="crep",
                   sig=(int(lost),) + tuple(int(h) for h in helpers),
                   trop=trop, chunk=chunk)
        self._q.put(job)
        return job.future

    def clay_repair(self, codec, lost: int, helpers,
                    planes: np.ndarray, chunk: int = 0) -> np.ndarray:
        return self.clay_repair_async(codec, lost, helpers,
                                      planes, chunk=chunk).result()

    def clay_decode_async(self, codec,
                          available: "Dict[int, np.ndarray]",
                          trop=None, chunk: int = 0) -> Future:
        """Survivor chunks {shard: [n]} -> Future of data planes [k, n]
        for an array codec (clay).  Jobs sharing a survivor signature
        coalesce like "dec", but along the intra-sub-chunk byte axis
        (see _dispatch_array) and keep EVERY survivor: with > k
        available the codec's single-erasure fast path reads d helpers
        instead of running the general multi-erasure decode."""
        self.start()
        sig = tuple(sorted(available))
        stacked = np.ascontiguousarray(np.stack(
            [np.asarray(available[i], dtype=np.uint8).reshape(-1)
             for i in sig]))
        job = _Job(codec, stacked, kind="cdec", sig=sig, trop=trop,
                   chunk=chunk)
        self._q.put(job)
        return job.future

    # -- worker -----------------------------------------------------------
    def _worker(self) -> None:
        job = None   # a job taken that did not fit the batch before it
        while True:
            if job is None:
                with tracing.span("queue.idle", q=self._span_q,
                                  seq=self.batches + 1):
                    job = self._q.get()
            if job is None:
                return
            with tracing.span("queue.coalesce", q=self._span_q,
                              seq=self.batches + 1):
                batch, job, stop = self._coalesce(job)
            self._run_batch(batch)
            if stop:
                return

    def _coalesce(self, first: _Job):
        """Greedy same-codec coalescing: drain whatever is queued,
        waiting at most one window for stragglers.  -> (the batch, the
        job that ended it because it belongs to another batch or None,
        whether the stop sentinel ended it)."""
        batch = [first]
        cols = first.planes.shape[1]
        waited = False
        while cols < self.max_batch_cols:
            try:
                nxt = self._q.get_nowait()
            except queue.Empty:
                if waited:
                    break
                waited = True
                try:
                    nxt = self._q.get(timeout=self.window_s)
                except queue.Empty:
                    break
            if nxt is None:
                return batch, None, True
            if (nxt.codec is not first.codec
                    or nxt.kind != first.kind
                    or nxt.sig != first.sig
                    or nxt.planes.shape[0] != first.planes.shape[0]):
                # different codec: flush current, start fresh
                return batch, nxt, False
            batch.append(nxt)
            cols += nxt.planes.shape[1]
        return batch, None, False

    def _apply_matrix(self, codec, batch: List[_Job],
                      stacked: np.ndarray) -> np.ndarray:
        """One device matmul for the whole batch (encode or decode).

        Contract: `stacked` arrives already covering-padded by
        _dispatch_batch — a raw width here would be a fresh XLA
        compile per distinct size (the shape-bucket ABI this helper
        sits under)."""
        gran = int(getattr(codec, "get_sub_chunk_count", lambda: 1)())
        assert stacked.shape[1] == shapebucket.covering(
            stacked.shape[1], gran), \
            f"unbucketed dispatch width {stacked.shape[1]} (gran={gran})"
        if batch[0].kind == "dec":
            rec, _bits = codec.recovery_matrix(list(batch[0].sig))
            if self.mesh is not None:
                self.mesh_batches += 1
                return self.mesh.recovery_gather(
                    np.asarray(rec, dtype=np.uint8), stacked)
            from ceph_tpu.ops import gf256_swar

            # the recovery matrix rides as an OPERAND: one program per
            # width serves every survivor signature (a baked matrix is
            # a compile per signature, in line on this one worker).
            # The planes are freshly uploaded per batch: donate them
            # so live HBM stays ~one batch deep through the pipeline
            return gf256_swar.gf_matmul_bytes(
                rec, stacked, donate=True, operand=True)
        coding_mat = getattr(codec, "coding", None)
        if self.mesh is not None and coding_mat is not None:
            self.mesh_batches += 1
            return self.mesh.encode_scatter(
                np.asarray(coding_mat, dtype=np.uint8), stacked)
        return np.asarray(codec.encode_array(stacked))

    def _dispatch_array(self, codec, batch: List[_Job],
                        widths: List[int]):
        """Array-codec (clay) batch: a job's row is S codewords (the
        stripes of a shard, `_Job.chunk` bytes each) of Z sub-chunks of
        s bytes, and codewords concatenate along the INTRA-sub-chunk
        byte axis — stripes of one job and jobs of one batch alike: the
        coupled-layer transforms are elementwise over that axis (each
        byte position within a sub-chunk is independent), while a raw
        byte concat (or a raw tail pad) would let the layer axis absorb
        a neighbour's bytes and corrupt every job in the batch.  The
        per-layer width is covering-padded to a pow2 so the encode's one
        program (words u32[k, Z, W]) and the flattened pair/solve matmul
        widths of repair and decode stay in the declared gf256_clay
        buckets.  Returns (per-job outputs in the rows' own
        layout, per-job crcs or None, the padded width)."""
        Z = int(codec.get_sub_chunk_count())
        kind = batch[0].kind
        rows = batch[0].planes.shape[0]
        # sub-chunks of a codeword a row holds: all Z, or ("crep") the
        # repair layers of the lost shard alone
        per = (len(codec.repair_layers(batch[0].sig[0]))
               if kind == "crep" else Z)
        geo: List[Tuple[int, int]] = []   # a job's (codewords, s)
        for j, w in zip(batch, widths):
            s = j.chunk // Z if j.chunk else w // per
            if s <= 0 or w % (per * s):
                raise ValueError(
                    f"{kind} row of {w} bytes is no whole number of "
                    f"codewords (chunk {j.chunk}, {per} sub-chunks)")
            geo.append((w // (per * s), s))
        svec = [S * s for S, s in geo]
        offs = list(itertools.accumulate(svec, initial=0))[:-1]
        s_pad = shapebucket.covering(sum(svec), 1)
        with tracing.span("batch.stack"):
            stacked = np.zeros((rows, per, s_pad), dtype=np.uint8)
            for j, o, (S, s) in zip(batch, offs, geo):
                stacked[:, :, o:o + S * s] = j.planes.reshape(
                    rows, S, per, s).transpose(0, 2, 1, 3).reshape(
                        rows, per, S * s)

        def unstack(out: np.ndarray) -> List[np.ndarray]:
            """[r, Z, s_pad] -> each job's [r, S*Z*s], its codewords
            one after another again."""
            r = out.shape[0]
            return [np.ascontiguousarray(
                out[:, :, o:o + S * s].reshape(r, Z, S, s).transpose(
                    0, 2, 1, 3)).reshape(r, S * Z * s)
                for o, (S, s) in zip(offs, geo)]

        crcs = None
        if kind == "crep":
            lost = batch[0].sig[0]
            helpers = list(batch[0].sig[1:])
            with tracing.span("batch.encode"):
                out = np.asarray(codec.repair_planes(
                    lost, helpers, stacked))
            outs = [o.reshape(-1) for o in unstack(out[None])]
        elif kind == "cdec":
            avail = list(batch[0].sig)
            with tracing.span("batch.encode"):
                data = np.asarray(codec.decode_planes(
                    avail, stacked.reshape(rows, Z * s_pad)))
            outs = unstack(data.reshape(codec.k, Z, s_pad))
        else:
            with tracing.span("batch.encode"):
                coding = np.asarray(codec.encode_array(
                    stacked.reshape(rows, Z * s_pad)))
            outs = unstack(coding.reshape(codec.m, Z, s_pad))
            if kind == "encp":
                # fused per-shard crc32c over the ORIGINAL per-job
                # chunk layout (crc is a byte stream over each chunk,
                # so the relayout from the s-axis batch is rebuilt
                # host-side; same device-rig honesty note as the flat
                # encp path)
                boffs = list(itertools.accumulate(widths, initial=0))[:-1]

                def full_planes() -> np.ndarray:
                    full = np.zeros((rows + codec.m, sum(widths)),
                                    dtype=np.uint8)
                    for i, (j, bo, w) in enumerate(
                            zip(batch, boffs, widths)):
                        full[:rows, bo:bo + w] = j.planes
                        full[rows:, bo:bo + w] = outs[i]
                    return full

                crcs = self._fused_crc(full_planes, boffs, widths)
        return outs, crcs, per * s_pad

    @staticmethod
    def _fused_crc(full_planes, offs: List[int],
                   widths: List[int]) -> np.ndarray:
        """Per-(job, shard) crc32c of a batch's data planes stacked
        over its coding planes (`full_planes()` builds them): the host
        relayout and the device pass of `crc32c_rows`, each under its
        span.  -> u32 [jobs, k+m]."""
        from ceph_tpu.ops.crc32c_device import crc32c_lanes, rows_layout

        with tracing.span("batch.crc_layout"):
            full = full_planes()
            rows, lens, inits = rows_layout(full, offs, widths)
        with tracing.span("batch.crc"):
            crcs = crc32c_lanes(rows, lens, inits)
        return crcs.reshape(-1, full.shape[0])[:len(widths)]

    def _run_batch(self, batch: List[_Job]) -> None:
        # publish the in-flight batch BEFORE any dispatch work (incl.
        # the failpoint: a barrier'd/stalled dispatch must show up in
        # the crash device section with its shapes); cleared by the
        # worker loop right after this call returns
        shapes = [list(j.planes.shape) for j in batch]
        self._inflight_info = {
            "kind": batch[0].kind, "jobs": len(batch),
            "shapes": shapes, "t0": time.monotonic()}
        try:
            self._dispatch_batch(batch, shapes)
        finally:
            self._inflight_info = None

    def _dispatch_batch(self, batch: List[_Job],
                        shapes: List[List[int]]) -> None:
        from ceph_tpu.core import failpoint as fp

        if fp.enabled("queue.batch.dispatch"):
            fp.failpoint("queue.batch.dispatch", jobs=len(batch),
                         kind=batch[0].kind)
        # the batch's span: its children are the stages below, what
        # none of them covers (counters, note_batch, compile blame) is
        # its self time; `seq` is set once `batches` has counted it
        with tracing.span(
                "queue.batch",
                causes=tuple(j.trop.id for j in batch
                             if j.trop is not None),
                q=self._span_q, kind=batch[0].kind,
                jobs=len(batch)) as sp:
            self._dispatch_stages(batch, shapes, sp.counts)

    def _dispatch_stages(self, batch: List[_Job],
                         shapes: List[List[int]], counts: Dict) -> None:
        t_start = time.monotonic()
        for j in batch:
            # queue wait: enqueue -> batch start; the coalescing
            # window is included — the op pays it either way
            self.perf.hinc("lat_encq_wait_us",
                           (t_start - j.t_enq) * 1e6)
        t_compute = t_start
        try:
            widths = [j.planes.shape[1] for j in batch]
            total = sum(widths)
            # EVERY dispatch — single jobs included — pads the
            # concatenated width up to its covering shape bucket
            # (shapebucket.covering: (a power of two) x (the codec's
            # column granularity)) so the device only ever sees the
            # family's DECLARED shapes: each distinct shape is a fresh
            # XLA compile, and an undeclared one is a rogue compile by
            # definition.  Flat codecs concatenate along the raw
            # column axis (column-local: padding cannot perturb real
            # columns — proven bit-identical in tier-1); array codecs
            # like clay take _dispatch_array, which concatenates along
            # the INTRA-sub-chunk byte axis instead (a raw byte concat
            # would let the layer axis absorb a neighbour's bytes).
            gran = 1
            get_subs = getattr(
                batch[0].codec, "get_sub_chunk_count", None)
            if get_subs is not None:
                gran = max(1, int(get_subs()))
            codec = batch[0].codec
            if gran > 1:
                outs, crcs, padded = self._dispatch_array(
                    codec, batch, widths)
                t_compute = time.monotonic()
                with tracing.span("batch.fanout"):
                    for i, j in enumerate(batch):
                        j.future.set_result(
                            (outs[i], crcs[i]) if batch[0].kind == "encp"
                            else outs[i])
            else:
                padded = shapebucket.covering(total, gran)
                k = batch[0].planes.shape[0]
                with tracing.span("batch.stack"):
                    stacked = np.zeros((k, padded), dtype=np.uint8)
                    off = 0
                    for j, w in zip(batch, widths):
                        stacked[:, off:off + w] = j.planes
                        off += w
                with tracing.span("batch.encode"):
                    coding = self._apply_matrix(codec, batch, stacked)
                if batch[0].kind == "encp":
                    # fused per-shard crc32c: one more device pass over
                    # the SAME batch (data planes + fresh coding
                    # planes); only the [jobs, k+m] u32 digests cross
                    # back — the payload stays put.  NOTE (device-rig
                    # honesty): this np concat + the crc row relayout
                    # are host moves on CPU rigs, folded into the
                    # already-counted upload; a real device rig must do
                    # them as jnp ops on the resident batch or it pays
                    # an uncounted round-trip — that port is the
                    # device-rig follow-up, not a counter change
                    crcs = self._fused_crc(
                        lambda: np.concatenate(
                            [stacked, np.asarray(coding)], axis=0),
                        list(itertools.accumulate(widths, initial=0))[:-1],
                        widths)
                    t_compute = time.monotonic()
                    with tracing.span("batch.fanout"):
                        off = 0
                        for i, (j, w) in enumerate(zip(batch, widths)):
                            j.future.set_result(
                                (coding[:, off:off + w], crcs[i]))
                            off += w
                else:
                    t_compute = time.monotonic()
                    with tracing.span("batch.fanout"):
                        off = 0
                        for j, w in zip(batch, widths):
                            j.future.set_result(coding[:, off:off + w])
                            off += w
            if batch[0].kind in ("encp", "dec", "cdec", "crep"):
                # the ONE h2d upload of the device-resident path: the
                # whole coalesced batch crosses together (stripe-tail
                # and pow2 padding are device-side zero-fill, not
                # transferred bytes — j.size is real payload)
                self.stats.inc("staged_batches")
                self.stats.inc("h2d_bytes",
                               sum(j.size for j in batch))
            self.batches += 1
            self.jobs += len(batch)
            self.batch_jobs[len(batch)] = (
                self.batch_jobs.get(len(batch), 0) + 1)
            if batch[0].kind in ("dec", "cdec", "crep"):
                self.dec_batch_jobs[len(batch)] = (
                    self.dec_batch_jobs.get(len(batch), 0) + 1)
            nbytes = sum(j.planes.nbytes for j in batch)
            self.bytes_in += nbytes
            counts.update(seq=self.batches, cols=total, padded=padded,
                          bytes=nbytes)
            t_done = time.monotonic()
            self.device_time_s += t_compute - t_start
            self.perf.hinc("lat_device_us",
                           (t_compute - t_start) * 1e6)
            self.perf.hinc("lat_encq_dispatch_us",
                           (t_done - t_compute) * 1e6)
            # device-runtime flight recorder + op-level compile blame:
            # a job whose [enqueue, compute-done] window overlapped a
            # live XLA compile was stalled BY that compile (one device
            # worker, one compiler) — annotate the op so slow-op
            # forensics can tell compile stalls from queue depth
            dw = devwatch.watch()
            dw.note_batch(batch[0].kind, len(batch), shapes,
                          t_compute - t_start)
            # compile-blame fast path: in steady state no compile is
            # live and none ended after the oldest job enqueued, so
            # the whole per-job overlap scan (span-ring walk under the
            # devwatch lock) is skipped
            if dw.compile_activity_since(
                    min(j.t_enq for j in batch)):
                for j in batch:
                    if j.trop is None:
                        continue
                    wait = dw.compile_overlap_s(j.t_enq, t_compute)
                    if wait <= 0:
                        continue
                    # annotation: timeline evidence only — it must
                    # NOT advance the stage-delta baseline (the
                    # adjacent commit/fanout histograms would read
                    # from the blame stamp instead of their stage)
                    j.trop.mark_event("compile_wait",
                                      f"{wait * 1e3:.1f}ms",
                                      annotation=True)
                    trk = getattr(j.trop, "tracker", None)
                    if trk is not None and trk.perf is not None:
                        trk.perf.hinc("lat_compile_wait_us",
                                      wait * 1e6)
        except BaseException as e:  # noqa: BLE001 — propagate to callers
            for j in batch:
                if not j.future.done():
                    j.future.set_exception(e)


_default: StripeBatchQueue | None = None
_default_lock = threading.Lock()


def default_queue() -> StripeBatchQueue:
    global _default
    with _default_lock:
        if _default is None:
            _default = StripeBatchQueue()
        return _default
