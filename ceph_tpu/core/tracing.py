"""The one span recorder, and the blkin spans that archive into it.

**The recorder** (`recorder()`, process-wide like `devwatch.watch()`,
always on, no option) keeps one bounded in-memory ring of records on
`time.monotonic_ns()`, the clock `OpTracker` and the stripe batch queue
already use:

- `with tracing.span(name, **counts):` records name, start, end, the
  thread's CPU time inside it (`time.thread_time_ns()`: wall minus CPU
  is the time the thread was off the CPU, waiting for the interpreter
  lock or blocked), thread, the span open on this thread when it
  started (its parent), the ids of what caused it where that is not the
  parent (`causes=`: a batch lists its jobs' tracked-op ids), and
  integer counts or short tags.  The same `with` opens a
  `jax.profiler.TraceAnnotation(name)`,
  inert unless a profiler trace is being taken, so in a traced run the
  span also lies on the xplane's host plane beside the device ops.  The
  xplane counts from its session's start at the rate of this clock:
  one matched pair (a ring span and its annotation) is the anchor
  between the two.  Names come from `SPANS` below.
- `OpTracker.unregister` files every concluded op's timeline as one
  `OP_RECORD`, so the stage times the `lat_*_us` histograms sum can be
  taken over a window; the objecter files each client op's as one
  `CLIENT_RECORD` when its caller has the reply, and `joined` lays the
  two of one `reqid` on one line from the client's creation of the op
  to its return.
- The ring counts what it overwrote; `batch_window` (what the
  benchmark's readers call) gives nothing for a range that reaches
  back to where records were lost.
- `dump_trace` on the admin socket writes the ring out.

**blkin spans** (reference: src/blkin/, Zipkin-style trace/span/parent
ids propagated with requests) stay behind the `tracing` option:
`Tracer.start_span(name, parent=...)` opens one, `span.annotate()` adds
timestamped events, `span.finish()` files it in the same ring.  Wire
propagation is by VALUE: `span.context()` returns (trace_id, span_id)
to embed in a message, and the receiving daemon opens its span with
`parent=that_context`.  `Tracer.dump(trace_id)` returns one trace's
spans, `Tracer.recent()` this tracer's tail of the ring.
"""

from __future__ import annotations

import itertools
import os
import threading
import time
from typing import (Any, Dict, List, NamedTuple, Optional, Sequence,
                    Tuple)

TraceContext = Tuple[int, int]  # (trace_id, span_id)

# -- stage-name registry -----------------------------------------------------
#
# Every stage event recorded on an op timeline (TrackedOp.mark_event)
# or annotated as a literal stage on a hot-path span must come from
# this table.  The name IS the contract between the instrumented site,
# the per-stage latency histogram it feeds (the osd.N.op `lat_*_us`
# counters — value below; '' = timeline-only), and every dump consumer
# (dump_historic_slow_ops, the mgr merge, cephtop, thrash forensics).
# A typo'd site is a dead timeline row that silently never feeds its
# histogram — cephlint's `span-discipline` check validates literal
# call-site names against this table (never baselineable, the
# failpoint-name-registry shape).
#
# Primary write-pipeline order (each histogram buckets the latency
# since the PREVIOUS timeline event, in microseconds):
#   initiated -> queued_for_pg -> qos_admitted -> reached_pg ->
#   [staged] -> admitted -> submitted -> commit -> [ack_gated]
#   -> commit_sent
# A served write end to end (`joined`; the per-layer metric that reads
# each stretch in brackets, op_* ms of `op_joined_mean`):
#   created -[op_send]-> initiated -[op_pre_encode]-> admitted
#   -[op_exec]-> encode_queued -[queue_wait + the cycle]-> encoded
#   -[op_fanout_wait]-> fanout_begun -[op_fanout]-> submitted
#   -[op_commit_wait]-> commit -[op_reply]-> commit_sent
#   -[op_reply_back]-> returned
STAGES: Dict[str, str] = {
    # client (objecter): CLIENT_RECORD's marks
    "created": "",             # the ObjecterOp is built (op_send_ms's
    #   start)
    "sent": "",                # op handed to the messenger, every send
    "reply_recv": "",          # the concluding MOSDOpReply handled
    "returned": "",            # the caller's thread has the reply
    #   (`result()` returns; op_reply_back_ms's end)
    "initiated": "",           # tracker entry created (messenger receive)
    # daemon dispatch
    "queued_for_pg": "lat_recv_us",      # decode -> sharded-queue entry
    # QoS admission (PR 13): the dmClock (or fifo A/B) scheduler
    # granted this op a workqueue slot — the delta since
    # queued_for_pg is the scheduler wait, the per-tenant fairness
    # number; reached_pg then measures only the dispatch residual
    "qos_admitted": "lat_qos_wait_us",
    "reached_pg": "lat_queue_us",        # queue wait: a shard picked it up
    # write pipeline
    "staged": "lat_staging_us",          # pinned staging-pool acquire
    "admitted": "lat_admission_us",      # _OidPipe admission FIFO grant
    # three annotations between admitted and submitted (mark_event(...,
    # annotation=True): lat_encode_fanout_us keeps its meaning)
    "encode_queued": "",       # encode_async returned (op_exec_ms's end)
    "encoded": "",             # the batch's result handed to this op
    "fanout_begun": "",        # the per-PG sequencer runs the fan-out
    #   (op_fanout_wait_ms's end, op_fanout_ms's start)
    "submitted": "lat_encode_fanout_us",  # exec+encode queued+fan-out sent
    "commit": "lat_commit_wait_us",      # last shard ack arrived
    "ack_gated": "lat_ack_gate_us",      # durable-ack gate released
    "commit_sent": "lat_reply_us",       # reply sent to the client
    # device runtime (PR 10): annotation, not a pipeline stage — the
    # overlap duration feeds lat_compile_wait_us DIRECTLY (an
    # EXTRA_HISTS entry), because the blame is "how long a live XLA
    # compile overlapped this op's encode wait", not a
    # since-previous-event delta
    "compile_wait": "",        # encode batch stalled behind a live compile
    # read path
    "parked": "",              # read parked on recover-on-read
    "read_sent": "lat_read_us",  # terminal for reads: execute -> reply
    #   (reads must NOT conclude as commit_sent — that would feed the
    #   whole read service time into lat_reply_us, which for writes
    #   measures only reply-send time)
    # peer-side span stages (cross-daemon children)
    "sub_write_recv": "",      # peer: MECSubWriteVec dispatched
    "store_commit": "",        # peer: merged store transaction durable
    "sub_read_served": "",     # peer: MECSubReadVec rows answered
    "note_persisted": "",      # peer: commit-note watermark on stable storage
    # terminal events (history admission; see optracker.TERMINAL_STAGES)
    "done": "",
    "eagain": "",              # retryable reply (peering gate, deadline sweep)
    "aborted": "",             # error reply or dispatch exception
    "daemon_shutdown": "",     # daemon went down with the op in flight
    "leaked": "",              # force-finished lifecycle leak (a bug)
}


# -- span-name registry --------------------------------------------------------
#
# Every name a `tracing.span(...)` site opens, with the stem of the
# per-layer metric of BENCHMARK.json that reads it ('' = none yet: the
# span is in the ring and on the profiler's host plane only).  Like
# STAGES, the name is the contract between the site and its readers;
# cephlint's `span-discipline` check holds literal call-site names to
# this table.  A metric is the SELF time of its spans (duration minus
# what direct children cover), summed over a window's batches.  Besides,
# `worker_offcpu_ms` reads the self time OFF the CPU (self wall minus
# self CPU) of every span below but `queue.idle`, `queue.coalesce` and
# `dev.wait`, whose waits are by design.
SPANS: Dict[str, str] = {
    # stripe batch queue: the worker thread's whole cycle
    "queue.idle": "worker_idle_ms",       # blocked in get(), nothing queued
    "queue.coalesce": "worker_idle_ms",   # first job taken -> batch closed
    "queue.batch": "batch_self_ms",       # one dispatch; counts seq, kind,
    #   jobs, cols, padded, bytes (and q, the queue's id, as idle and
    #   coalesce); causes = the jobs' tracked-op ids; self time =
    #   counters, note_batch, compile blame
    "batch.stack": "batch_stack_ms",      # jobs copied into one padded array
    "batch.encode": "batch_self_ms",      # the matmul call (encode or decode)
    "batch.crc_layout": "batch_crc_layout_ms",  # concat + crc row relayout
    "batch.crc": "batch_self_ms",         # the fused crc32c call
    "batch.fanout": "batch_self_ms",      # results handed to the futures
    # kernels, host side (children of whatever stage called them)
    "dev.dispatch": "dev_dispatch_ms",    # instrumented_jit: upload of numpy
    #   operands and enqueue; count: family
    "dev.wait": "dev_wait_ms",            # devwatch.fetch: blocked until the
    #   device is done, fetch included
    # clay (ec/clay.py): the array codec's encode, below `batch.encode` in
    # the queue's array branch.  On a device engine the whole encode is
    # ONE jitted call under `clay.mds` (its `dev.dispatch` and `dev.wait`
    # below it), whose self time is the host work left around the call;
    # on the native engine the three steps each make one call of the
    # GF(2^8) engine, and their self time is the host's index gathers,
    # stacks and scatters around it
    "clay.uncouple": "clay_host_ms",      # native engine: data nodes C -> U;
    #   counts pairs (coupled symbols transformed, node x layer) and bytes
    #   (the pair matmul's two input rows)
    "clay.mds": "clay_host_ms",           # the one program of a device
    #   engine, or the native engine's scalar MDS code over every layer at
    #   once; counts layers, bytes (the kk input rows)
    "clay.couple": "clay_host_ms",        # native engine: parity column
    #   U -> C; pairs, bytes
    "clay.repair": "",                    # repair_planes: one node from the
    #   repair layers of d helpers; counts layers, bytes (the helpers')
    "clay.solve": "",                     # _solve_unknowns: the erased nodes'
    #   U from kk known rows (repair and layered decode); counts rows
    #   (nodes solved for) and bytes (the known rows')
    # the codec's monotonic total (clay.dev_calls(): a counter, not a
    # span, registered here as the CRUSH totals below are)
    "clay.dev_calls": "clay_dev_calls_per_batch",   # calls of the GF
    #   engine the codec made, a device call each on the chip: 1 an encode
    #   there (3 on the native engine)
    # CRUSH sweep
    "crush.sweep": "",                    # sweep_device's call; counts ids,
    #   chunk, numrep, mode (firstn/indep), the plan it ran: cap, cap2
    #   (lanes the budgeted and the exact stage can take), budget; and the
    #   descent levels of the stage programs it ran by how each reads its
    #   bucket rows: const, onehot (from the level's static frontier),
    #   gather (by the bucket index); and by how each draws: draw_fast
    #   (fastcmp: the max-hash item, delta > 0), draw_class (the max-hash
    #   item of each weight class through the draw tables), draw_table
    #   (every item through the draw tables), draw_limb (every item by the
    #   u32-limb division)
    # the mapper's monotonic totals (mapper.sweep_totals(): counters, not
    # spans, registered here so that their names are held to one table)
    "crush.ids": "crush_mid_lanes_per_id",          # ids swept: the divisor
    #   of both ratios
    "crush.mid_lanes": "crush_mid_lanes_per_id",    # lanes into stage 2
    "crush.slow_lanes": "crush_slow_lanes_per_id",  # lanes into stage 3
    "crush.full_draws": "crush_full_draws_per_id",  # bucket items whose
    #   true straw2 draw (table or limb path) the stage programs computed:
    #   a stage program's full draws a lane, from static counts, times the
    #   lanes that entered the stage; the exact stage's rolled retry loop
    #   counts at one pass of its body, so its part is a lower bound
}

# a concluded op's timeline, filed by OpTracker.unregister: read by
# op_pre_encode_ms, op_commit_wait_ms and op_reply_ms, and joined
OP_RECORD = "op"
# a client op's timeline, filed by the objecter when its caller has the
# reply: joined to its primary's OP_RECORD by reqid, read by the op_*
# metrics of `op_joined_mean`
CLIENT_RECORD = "client_op"

# -- the recorder --------------------------------------------------------------

clock = time.monotonic_ns
cpu_clock = time.thread_time_ns

# a record: one tuple, indexed by these (SEQ: its place in the order
# in which records were filed, i.e. closed; CPU: the thread's CPU
# nanoseconds between T0 and T1, None where not read)
ID, NAME, T0, T1, THREAD, PARENT, CAUSES, COUNTS, SEQ, CPU = range(10)

# Ring size.  Today's write cell makes 11 batches a second of 12 spans
# (idle, coalesce, batch, five stages, two dispatches, two waits) and
# 13 op and 13 client records: 8,700 records in a 51 s window, and
# 1,280 op and client records in the 13 s check after it.  Six times
# that is 60,000.
RING = 1 << 16

_annotation = None


def _trace_annotation():
    """jax's TraceAnnotation, imported at the first span: `core/` does
    not import jax at import time, and every span site runs beside jax."""
    global _annotation
    if _annotation is None:
        from jax.profiler import TraceAnnotation

        _annotation = TraceAnnotation
    return _annotation


class _Thread:
    """Per-thread state: its id and the ids of the spans open on it."""
    __slots__ = ("tid", "open")

    def __init__(self) -> None:
        self.tid = threading.get_ident()
        self.open: List[int] = []


class _OpenSpan:
    """`with tracing.span("batch.stack", cols=n):` files one record in
    the process's recorder when the block ends, however it ends; see
    the module's docstring.  `name` is a literal from SPANS; `counts`
    may be added to until the block ends (`sp.counts`)."""
    __slots__ = ("rec", "name", "counts", "causes", "id", "t0", "t1",
                 "_cpu0", "_ann", "_th")

    def __init__(self, name: str, causes: Tuple = (), **counts) -> None:
        self.rec = _recorder
        self.name = name
        self.causes = causes
        self.counts = counts

    def __enter__(self) -> "_OpenSpan":
        rec = self.rec
        try:
            th = rec._tls.th
        except AttributeError:
            th = rec._tls.th = _Thread()
        self._th = th
        self.id = i = next(rec._ids)
        th.open.append(i)
        # an annotation opened while no trace is taken is dropped by
        # the profiler anyway: make one only under a trace (the check
        # costs nothing, the object a third of a microsecond)
        ann = _annotation or _trace_annotation()
        if ann.is_enabled():
            self._ann = ann = ann(self.name)
            ann.__enter__()
        else:
            self._ann = None
        # the CPU reads lie inside the wall ones: a span that never
        # leaves the CPU reads CPU <= wall
        self.t0 = clock()
        self._cpu0 = cpu_clock()
        return self

    def __exit__(self, *exc) -> None:
        cpu = cpu_clock() - self._cpu0
        self.t1 = t1 = clock()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        th = self._th
        opened = th.open
        opened.pop()
        rec = self.rec
        seq = next(rec._seqs)   # Recorder._file, in line: the hot path
        rec._ring[seq % rec.capacity] = (
            self.id, self.name, self.t0, t1, th.tid,
            opened[-1] if opened else 0, self.causes, self.counts, seq,
            cpu)

    @property
    def seconds(self) -> float:
        return (self.t1 - self.t0) / 1e9


span = _OpenSpan


class Recorder:
    def __init__(self, capacity: int = RING) -> None:
        self.capacity = capacity
        self._ring: List[Optional[Tuple]] = [None] * capacity
        # filing takes no lock: `next` of a counter is one step of the
        # interpreter, it hands every record a slot of its own, and a
        # slot is written with one store
        self._seqs = itertools.count()
        self._ids = itertools.count(1)
        self._tls = threading.local()
        # upper half of the ids that cross the wire (blkin): set once,
        # so that two processes' counters do not collide
        self._salt = (int.from_bytes(os.urandom(4), "little") | 1) << 32
        # one reading of both clocks, for a dump's reader who wants dates
        self.anchor = (clock(), time.time_ns())

    def next_id(self) -> int:
        return next(self._ids)

    def wire_id(self) -> int:
        """A nonzero 63-bit id, unique across processes as far as 31
        random bits make it."""
        return (self._salt | next(self._ids)) & ((1 << 63) - 1)

    def span(self, name: str, causes: Tuple = (), **counts) -> _OpenSpan:
        """`tracing.span` into this recorder (the tests' own rings)."""
        sp = _OpenSpan(name, causes, **counts)
        sp.rec = self
        return sp

    def op(self, op) -> None:
        """A concluded TrackedOp's timeline as one record; its events
        are (seconds since the op's start, stage, detail)."""
        self._file(op.id, OP_RECORD, int(op.start * 1e9),
                   int(op.done_at * 1e9), threading.get_ident(), 0, (),
                   {"desc": op.desc, "reqid": op.reqid,
                    "events": tuple(op.events)})

    def client(self, reqid: str, events: Tuple[Tuple[int, str], ...]) -> None:
        """A client op's timeline as one CLIENT_RECORD, from its first
        event (`created`) to its last; its events are (ns on this
        clock, stage)."""
        self._file(self.next_id(), CLIENT_RECORD, events[0][0],
                   events[-1][0], threading.get_ident(), 0, (),
                   {"reqid": reqid, "events": events})

    def _file(self, id_: int, name: str, t0: int, t1: int, thread: int,
              parent: int, causes: Tuple, counts: Dict[str, Any],
              cpu: Optional[int] = None) -> None:
        seq = next(self._seqs)
        self._ring[seq % self.capacity] = (
            id_, name, t0, t1, thread, parent, causes, counts, seq, cpu)

    def held(self) -> Tuple[List[Tuple], int]:
        """(the records the ring holds, in the order they were filed;
        `lost_until`: 0, or once the ring has wrapped the end of its
        oldest record, which no overwritten record outlasted by more
        than the moment between two threads' filing).  A range that
        starts at or before `lost_until` may have lost records."""
        recs = sorted((r for r in list(self._ring) if r is not None),
                      key=lambda r: r[SEQ])
        wrapped = bool(recs) and recs[0][SEQ] > 0
        return recs, recs[0][T1] if wrapped else 0

    @property
    def overwritten(self) -> int:
        """Records filed and since overwritten."""
        recs, _ = self.held()
        return recs[0][SEQ] if recs else 0

    def dump(self, count: int = 1000) -> Dict[str, Any]:
        """The ring's tail, for `dump_trace` on the admin socket."""
        recs, lost_until = self.held()

        def row(r: Tuple) -> Dict[str, Any]:
            out = {"id": r[ID], "name": r[NAME], "start_ns": r[T0],
                   "duration_ns": r[T1] - r[T0], "thread": r[THREAD],
                   "parent": r[PARENT], "causes": list(r[CAUSES])}
            out.update(r[COUNTS])
            return out

        tail = recs[-count:]
        return {
            "clock": "monotonic_ns",
            "anchor": {"monotonic_ns": self.anchor[0],
                       "unix_ns": self.anchor[1]},
            "capacity": self.capacity,
            "held": len(recs),
            "overwritten": recs[0][SEQ] if recs else 0,
            "lost_until_ns": lost_until,
            "spans": [row(r) for r in tail
                      if r[NAME] not in (OP_RECORD, CLIENT_RECORD)],
            "ops": [row(r) for r in tail if r[NAME] == OP_RECORD],
            "client_ops": [row(r) for r in tail
                           if r[NAME] == CLIENT_RECORD],
        }


_recorder = Recorder()


def recorder() -> Recorder:
    return _recorder


def self_ns(records: Sequence[Tuple]) -> Dict[int, int]:
    """id -> a record's duration minus the part of it that its direct
    children (records naming it as parent, so spans of its own thread)
    cover, each stretch counted once."""
    kids: Dict[int, List[Tuple[int, int]]] = {}
    for r in records:
        if r[PARENT]:
            kids.setdefault(r[PARENT], []).append((r[T0], r[T1]))
    out = {}
    for r in records:
        covered, edge = 0, r[T0]
        for s, e in sorted(kids.get(r[ID], ())):
            s, e = max(s, edge), min(e, r[T1])
            if e > s:
                covered += e - s
                edge = e
        out[r[ID]] = r[T1] - r[T0] - covered
    return out


def self_cpu_ns(records: Sequence[Tuple]) -> Dict[int, int]:
    """id -> a span's CPU time minus its direct children's, which ran
    on its thread inside it: `self_ns` on the CPU clock, for the
    records that carry one."""
    kids: Dict[int, int] = {}
    for r in records:
        if r[PARENT] and r[CPU] is not None:
            kids[r[PARENT]] = kids.get(r[PARENT], 0) + r[CPU]
    return {r[ID]: r[CPU] - kids.get(r[ID], 0)
            for r in records if r[CPU] is not None}


def joined(clients: Sequence[Tuple],
           recs: Sequence[Tuple]) -> List[List[Tuple[int, str]]]:
    """Each CLIENT_RECORD of `clients` laid on one line, in time order,
    with its primary's OP_RECORD of the same reqid among `recs` (in
    filing order, i.e. of conclusion): the first one that concluded
    with `commit_sent`, whose reply the client took, else the last one.
    A resend makes several: after an EAGAIN the attempt that committed
    is the later one; a resend of an op that was slow to answer (the
    objecter resends after `resend_interval`) is answered from the log
    after the original committed, and did none of its work.  Both
    records are on this clock, which is system-wide, so the line holds
    across processes of one host.  A client record with no op record
    is left out."""
    pick: Dict[str, Tuple] = {}
    for r in recs:
        if r[NAME] != OP_RECORD or not r[COUNTS].get("reqid"):
            continue
        prev = pick.get(r[COUNTS]["reqid"])
        if prev is None or prev[COUNTS]["events"][-1][1] != "commit_sent":
            pick[r[COUNTS]["reqid"]] = r
    out = []
    for c in clients:
        p = pick.get(c[COUNTS]["reqid"])
        if p is not None:
            line = list(c[COUNTS]["events"]) + [
                (p[T0] + round(s * 1e9), stage)
                for s, stage, _detail in p[COUNTS]["events"]]
            line.sort(key=lambda e: e[0])
            out.append(line)
    return out


class BatchWindow(NamedTuple):
    """What the ring holds of the batches `seq_lo < seq <= seq_hi`."""
    batches: int               # `queue.batch` spans in the range
    self_ns: Dict[str, int]    # self time by span name, over those
    #   spans, the idle and coalesce before each, and all below them
    ops: List[Tuple]           # op records concluded while they ran
    self_cpu_ns: Dict[str, int]   # self CPU time by span name, as
    #   self_ns, over the spans that carry a CPU time
    joined: List[List[Tuple[int, str]]]   # `joined` lines of the client
    #   records concluded while they ran


def batch_window(seq_lo: int, seq_hi: int,
                 rec: Optional[Recorder] = None) -> Optional[BatchWindow]:
    """The join between a counter window and the ring: the queue's
    `batches` before and after a window select the `queue.batch` spans
    (and the `queue.idle` / `queue.coalesce` that led to each) by their
    `seq` count.  None where a batch of the range is not in the ring,
    or the range reaches back to a record the ring overwrote."""
    recs, lost_until = (rec or _recorder).held()
    counted = [r for r in recs
               if seq_lo < r[COUNTS].get("seq", seq_lo) <= seq_hi]
    # a process serves through one queue; where it made more (tests),
    # the range is the newest one's
    q = counted[-1][COUNTS].get("q") if counted else None
    tree = {r[ID]: r for r in counted if r[COUNTS].get("q") == q}
    roots = [r for r in tree.values() if r[NAME] == "queue.batch"]
    if not roots or len(roots) != seq_hi - seq_lo:
        return None
    t0 = min(r[T0] for r in tree.values())
    t1 = max(r[T1] for r in roots)
    if t0 <= lost_until:
        return None
    # children close before their parents, so one pass from the newest
    # record back finds every descendant
    for r in reversed(recs):
        if r[PARENT] in tree:
            tree[r[ID]] = r
    spans = list(tree.values())
    own, own_cpu = self_ns(spans), self_cpu_ns(spans)
    by_name: Dict[str, int] = {}
    cpu_by_name: Dict[str, int] = {}
    for r in spans:
        by_name[r[NAME]] = by_name.get(r[NAME], 0) + own[r[ID]]
        if r[ID] in own_cpu:
            cpu_by_name[r[NAME]] = (cpu_by_name.get(r[NAME], 0)
                                    + own_cpu[r[ID]])
    ops = [r for r in recs if r[NAME] == OP_RECORD and t0 <= r[T1] <= t1]
    clients = [r for r in recs
               if r[NAME] == CLIENT_RECORD and t0 <= r[T1] <= t1]
    return BatchWindow(len(roots), by_name, ops, cpu_by_name,
                       joined(clients, recs))


# -- blkin spans (behind the `tracing` option) -----------------------------------

class Span:
    __slots__ = ("tracer", "name", "trace_id", "span_id", "parent_id",
                 "start", "end", "annotations")

    def __init__(self, tracer: "Tracer", name: str, trace_id: int,
                 span_id: int, parent_id: int, at: int = 0) -> None:
        self.tracer = tracer
        self.name = name
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self.start = at or clock()
        self.end = 0
        self.annotations: List[Tuple[int, str]] = []

    # `at`: an instant on this clock that the caller has already read
    # (the objecter's client timeline), so the span reads none itself
    def annotate(self, what: str, at: int = 0) -> None:
        self.annotations.append((at or clock(), what))

    def context(self) -> TraceContext:
        """The wire-propagatable identity of this span."""
        return (self.trace_id, self.span_id)

    def finish(self, at: int = 0) -> None:
        if not self.end:
            self.end = at or clock()
            self.tracer._archive(self)

    def __enter__(self) -> "Span":
        return self

    def __exit__(self, *exc) -> None:
        self.finish()


def _unix_s(t_ns: int) -> float:
    mono, unix = _recorder.anchor
    return (t_ns - mono + unix) / 1e9


def _span_dict(r: Tuple) -> Dict:
    return {
        "name": r[NAME],
        "trace_id": f"{r[CAUSES][0]:016x}",
        "span_id": f"{r[ID]:016x}",
        "parent_id": f"{r[PARENT]:016x}" if r[PARENT] else None,
        "start": _unix_s(r[T0]),
        "duration_s": round((r[T1] - r[T0]) / 1e9, 6),
        "annotations": [{"at": _unix_s(at), "what": w}
                        for at, w in r[COUNTS]["annotations"]],
    }


class Tracer:
    """A daemon's handle on the recorder for blkin spans; a disabled
    tracer files nothing."""

    def __init__(self, name: str = "", enabled: bool = True) -> None:
        self.name = name
        self.enabled = enabled
        self._key = _recorder.next_id()   # marks this tracer's records

    def start_span(self, name: str,
                   parent: Optional[TraceContext] = None,
                   at: int = 0) -> Span:
        if parent is not None:
            trace_id, parent_id = parent
        else:
            trace_id, parent_id = _recorder.wire_id(), 0
        return Span(self, name, trace_id, _recorder.wire_id(), parent_id,
                    at)

    def _archive(self, span: Span) -> None:
        if self.enabled:
            _recorder._file(
                span.span_id, span.name, span.start, span.end,
                threading.get_ident(), span.parent_id, (span.trace_id,),
                {"tracer": self._key,
                 "annotations": tuple(span.annotations)})

    # -- query (admin-socket surface) --------------------------------------
    def _mine(self) -> List[Tuple]:
        return [r for r in _recorder.held()[0]
                if r[COUNTS].get("tracer") == self._key]

    def dump(self, trace_id: int) -> List[Dict]:
        spans = [r for r in self._mine() if r[CAUSES][0] == trace_id]
        return [_span_dict(r) for r in sorted(spans, key=lambda r: r[T0])]

    def recent(self, n: int = 100) -> List[Dict]:
        return [_span_dict(r) for r in self._mine()[-n:]]


def trace_id_of(reqid: str) -> int:
    """Deterministic trace id from a request id: every daemon touching
    one client op derives the SAME trace id without any wire change —
    the reqid IS the correlator (the reference's osd_reqid_t threading
    through op tracking)."""
    from ceph_tpu.core.crc import crc32c

    b = reqid.encode()
    return ((crc32c(b) << 32) | crc32c(b, 0xA5A5A5A5)) | 1
