"""DeviceWatch — process-wide XLA compile/dispatch observability.

The device runtime was the last observability black box: PR 8/9 can
attribute every microsecond of an op's life EXCEPT the ones XLA spends
compiling or executing a kernel, and that blindness has cost real
engineering time (the PR 3 CRUSH-sweep recompile hunt, the PR 4 slow
re-tier of compile-heavy tests, PR 9's discarded pair-0 "XLA-compile
skew" warmup trial).  Reference shape: the ``dout`` gather ring +
fatal-signal crash dump (src/log/Log.cc, src/global/signal_handler.cc)
— every interesting device event is recorded cheaply ALWAYS, and a
stall or crash leaves a diagnosable corpse.

One process-wide :class:`DeviceWatch` (``watch()``) owns:

- ``instrumented_jit(fn, family=...)`` / ``instrumented_pallas_call``
  — the ONLY sanctioned jit/pallas entry points in ``ceph_tpu``
  (cephlint ``no-unwatched-jit``, never baselineable).  Per kernel
  FAMILY they record compile count, compile wall seconds, the input
  shape/dtype signature, and cache hit/miss (a call whose signature
  this wrapper has not seen = trace re-entry = compile); cache hits
  feed a per-family log2 execute-time histogram.
- recompile-storm detection: >= ``tpu_recompile_storm_min_sigs``
  compiles of one family with DISTINCT signatures inside a
  ``tpu_recompile_storm_window`` sliding window raises a cluster-log
  WARN naming the family and the churning dimension (the PR 3 pow2
  high-water fix, as a standing alarm instead of a one-off hunt).
- a steady-state guard (:meth:`steady_state`): the conftest arms the
  assertion for all of tier-1 (the lockdep shape), and any code that
  has finished warmup wraps its steady section — a compile inside the
  section lands in :data:`GUARD_VIOLATIONS` and fails the test.
- compile-overlap queries (:meth:`compile_overlap_s`) so the
  StripeBatchQueue can blame an op's stall on a live compile
  (``compile_wait`` timeline annotation + ``lat_compile_wait_us``).
- the flight recorder: compile and batch-dispatch events ride a
  bounded ring here AND the core log gather ring (subsys ``tpu``),
  and :meth:`device_state` snapshots queue depth / staging occupancy /
  the in-flight batch / last compiles for ``CrashArchive.record()``.
- surfaces: a real :class:`PerfCounters` set registered per daemon as
  ``osd.N.xla``, the ``device compile dump`` admin/mgr command, and a
  family-labeled Prometheus export (``ceph_xla_*`` with the
  ``le="+Inf"`` terminal-bucket rule PR 9 established).

Timing honesty: tier-1 runs on CPU where dispatch is synchronous, so
the execute histograms are wall time around the jit call.  On an
accelerator dispatch is asynchronous: the hit-path number is DISPATCH
wall, not device time — the same caveat every bench in this repo
documents.
"""

from __future__ import annotations

import collections
import contextlib
import functools
import threading
import time
from typing import Any, Callable, Deque, Dict, List, Optional, Tuple

import numpy as np

from ceph_tpu.core import tracing
from ceph_tpu.core.lockdep import make_lock
from ceph_tpu.core.perf import PerfCounters

# steady-state guard evidence (the LOOP_STALLS / LEAKS sanitizer
# shape): compiles observed inside a declared steady-state section.
# tests/conftest.py asserts this empty after every tier-1 test.
GUARD_VIOLATIONS: List[str] = []

# flight-recorder geometry
_EVENT_RING = 256        # compile + batch events kept for dumps
_SPAN_RING = 512         # finished compile spans kept for overlap math
_SIGS_KEPT = 32          # distinct signatures listed per family dump

# storm defaults.  The 8/60s total-signature threshold was calibrated
# against a measured cold start (ROUND10): a healthy pow2-padded
# process compiles ~5 distinct crc shapes and ~2-3 mapper shapes in
# its first minute — bounded warmup, not churn — so the detector had
# to tolerate declared cold ladders heuristically.  With the shape
# ABI (tpu/shapebucket.py) classifying every compile, DECLARED
# signatures keep that loose threshold (a cold ladder is finite by
# construction) while ROGUE signatures — undeclared, a bug by
# definition — trip at a much tighter count: three distinct rogue
# shapes of one family inside a minute is churn, never warmup.
DEFAULT_STORM_WINDOW_S = 60.0
DEFAULT_STORM_MIN_SIGS = 8
DEFAULT_STORM_MIN_ROGUE_SIGS = 3


def _sig_of(v: Any, static: bool = False) -> Tuple:
    """One argument's signature atom, mirroring jax's compile-cache
    key: shape/dtype for array-likes (ndarray, jax array, tracer);
    VALUE only for declared-static arguments (each static value IS a
    distinct compile in jax too); plain dynamic Python scalars key by
    TYPE — jax traces them as weak-typed constants and does NOT
    recompile per value, so neither may this watcher (a value-keyed
    scalar would inflate compile counts, grow the seen set unbounded,
    and raise false storms on a healthy kernel — review finding)."""
    shape = getattr(v, "shape", None)
    dtype = getattr(v, "dtype", None)
    if shape is not None and dtype is not None:
        try:
            return ("arr", str(dtype), tuple(int(d) for d in shape))
        except TypeError:  # symbolic dims: fall through to type name
            return ("arr", str(dtype), str(shape))
    if static:
        return ("static", repr(v))
    if isinstance(v, (bool, int, float, complex)):
        return ("py", type(v).__name__)
    if v is None or isinstance(v, str):
        # strings/None cannot be traced dynamically: they are de
        # facto static whether declared or not
        return ("val", repr(v))
    if isinstance(v, bytes):
        return ("val", f"bytes[{len(v)}]")
    return ("obj", type(v).__name__)


def signature(args: Tuple, kwargs: Dict[str, Any],
              static_argnums: Tuple[int, ...] = (),
              static_argnames: Tuple[str, ...] = ()) -> Tuple:
    """Shape/dtype signature of one call — the compile-cache key this
    watcher tracks (mirrors jax's own: a novel signature re-traces;
    declared-static args key by value, dynamic scalars by type)."""
    sig = tuple(_sig_of(a, static=i in static_argnums)
                for i, a in enumerate(args))
    if kwargs:
        sig += tuple((k, _sig_of(v, static=k in static_argnames))
                     for k, v in sorted(kwargs.items()))
    return sig


_SCALAR_KINDS = ("val", "obj", "py", "static")


def sig_str(sig: Tuple) -> str:
    """Human rendering: ``uint8[2,4096], n=512``."""
    parts = []
    for atom in sig:
        if len(atom) == 3 and atom[0] == "arr":
            _k, dt, shape = atom
            dims = ",".join(str(d) for d in shape) \
                if isinstance(shape, tuple) else str(shape)
            parts.append(f"{dt}[{dims}]")
        elif len(atom) == 2 and atom[0] in _SCALAR_KINDS:
            parts.append(str(atom[1]))
        else:  # kwarg pair: (name, atom)
            parts.append(f"{atom[0]}={sig_str((atom[1],))}")
    return ", ".join(parts)


def _churn_dim(sigs: List[Tuple]) -> str:
    """Name the churning dimension across a storm's distinct
    signatures: the first arg position (and shape axis) whose values
    differ — the actionable pointer ("pad arg0.shape[1] to pow2")."""
    if not sigs:
        return "unknown"
    lens = {len(s) for s in sigs}
    if len(lens) != 1:
        return "arg-structure (argument count varies)"
    for i in range(len(sigs[0])):
        atoms = {s[i] for s in sigs}
        if len(atoms) <= 1:
            continue
        shapes = [a[2] for a in atoms
                  if len(a) == 3 and a[0] == "arr"
                  and isinstance(a[2], tuple)]
        if len(shapes) == len(atoms):
            ranks = {len(sh) for sh in shapes}
            if len(ranks) == 1:
                axes = [ax for ax in range(ranks.pop())
                        if len({sh[ax] for sh in shapes}) > 1]
                if axes:
                    return f"arg{i}.shape[{axes[0]}]" + (
                        f" (+{len(axes) - 1} more axes)"
                        if len(axes) > 1 else "")
            return f"arg{i}.shape (rank varies)"
        return f"arg{i}"
    return "unknown"


class _Family:
    __slots__ = ("sigs", "compiles", "compile_s", "hits", "dispatches",
                 "traces", "warmup", "cold", "rogue", "persist_hits")

    def __init__(self) -> None:
        self.sigs: "collections.OrderedDict[Tuple, int]" = \
            collections.OrderedDict()  # sig -> compile count
        self.compiles = 0
        self.compile_s = 0.0
        self.hits = 0
        self.dispatches = 0
        self.traces = 0  # pallas_call trace re-entries
        # compile classification against the declared shape-bucket ABI
        # (tpu/shapebucket.py): warmup = declared bucket compiled
        # inside a DeviceWarmup pass; cold = declared but first hit
        # outside warmup; rogue = UNDECLARED signature (a bug)
        self.warmup = 0
        self.cold = 0
        self.rogue = 0
        # compiles this process resolved from the persistent on-disk
        # XLA cache (a previous process paid the wall, we didn't)
        self.persist_hits = 0


class DeviceWatch:
    """Process-wide device-runtime watcher; see module docstring."""

    def __init__(self) -> None:
        self._lock = make_lock("devwatch")
        self.perf = PerfCounters("tpu.xla")
        self.perf.add_u64_counter(
            "compile_total", "XLA compiles observed (all families)")
        self.perf.add_time_avg(
            "compile_seconds", "wall seconds spent compiling")
        self.perf.add_u64_gauge(
            "distinct_shapes", "distinct compile signatures, all families")
        self.perf.add_u64_counter(
            "cache_hits", "jit calls served by an existing compile")
        self.perf.add_u64_counter(
            "recompile_storms", "recompile-storm WARNs raised")
        self.perf.add_u64_counter(
            "rogue_compiles",
            "compiles with a signature OUTSIDE the declared bucket "
            "set (shape-bucket ABI violation)")
        self.perf.add_u64_counter(
            "warmup_compiles",
            "declared-bucket compiles paid inside a warmup pass")
        self.perf.add_u64_counter(
            "cache_persist_hits",
            "compiles served from the persistent on-disk XLA cache "
            "(a previous process paid the wall)")
        self.perf.add_u64_counter(
            "cache_persist_misses",
            "persistent-cache lookups that missed (wall paid here)")
        self._fams: Dict[str, _Family] = {}
        # flight recorder: (t_mono, kind, family, detail) —
        # kind in ("compile", "batch", "trace", "storm")
        self._events: Deque[Tuple[float, str, str, str]] = \
            collections.deque(maxlen=_EVENT_RING)
        # finished compile spans (t0, t1) + live compiles for the
        # op-blame overlap query; monotonic clock throughout (the
        # queue's job stamps are monotonic too)
        self._spans: Deque[Tuple[float, float]] = \
            collections.deque(maxlen=_SPAN_RING)
        self._live: Dict[int, Tuple[str, float]] = {}
        self._live_seq = 0
        # storm detection: (t, family, sig) of recent compiles
        self._recent: Deque[Tuple[float, str, Tuple]] = \
            collections.deque(maxlen=_SPAN_RING)
        self.storm_window_s = DEFAULT_STORM_WINDOW_S
        self.storm_min_sigs = DEFAULT_STORM_MIN_SIGS
        self.storm_min_rogue_sigs = DEFAULT_STORM_MIN_ROGUE_SIGS
        # warmup classification: >0 while a DeviceWarmup pass runs
        self._warmup = 0
        # last published DeviceWarmup stats (families warmed, seconds
        # spent, buckets skipped) — the osd.N.xla dump's warmup section
        self.warmup_stats: Optional[Dict[str, Any]] = None
        # persistent-cache events (jax monitoring listener, installed
        # by shapebucket.setup_compile_cache)
        self._persist_hits = 0
        self._persist_misses = 0
        # monotonic stamp of the last compile END (the blame fast
        # path's lock-free pre-check; 0.0 = never compiled)
        self.last_compile_end = 0.0
        # cumulative seconds host threads spent blocked in fetch()
        # (`dev.wait` spans): what the queue's device_busy_pct gauge
        # is the rate of
        self.wait_s = 0.0
        self._storm_last: Dict[str, float] = {}  # family -> last WARN t
        self.storms: List[Dict[str, Any]] = []   # bounded below
        self._steady = 0  # steady-state section depth
        self._log = None  # core.log.Log (gather ring + cluster WARN)
        self._queue = None  # StripeBatchQueue override (tests)

    # -- wiring ------------------------------------------------------------
    def attach_log(self, log) -> None:
        """Point the flight recorder at a context's Log: compile/batch
        events land in its gather ring (subsys ``tpu``) and storm
        WARNs ride its cluster channel.  Latest attach wins (vstart
        daemons share one Context/Log, and ``revive_osd`` constructs
        a fresh OSDService whose init re-attaches — the PR 8/9
        dead-feed discipline); a Log whose daemon died still records
        to its ring and has no live ``cluster_cb`` to misroute (the
        cluster callback is unwired repo-wide today)."""
        self._log = log

    def attach_queue(self, queue) -> None:
        """Override the queue ``device_state`` snapshots (tests);
        None restores the process default queue."""
        self._queue = queue

    def configure(self, window_s: Optional[float] = None,
                  min_sigs: Optional[int] = None,
                  min_rogue_sigs: Optional[int] = None) -> None:
        if window_s is not None and window_s > 0:
            self.storm_window_s = float(window_s)
        if min_sigs is not None and min_sigs > 0:
            self.storm_min_sigs = int(min_sigs)
        if min_rogue_sigs is not None and min_rogue_sigs > 0:
            self.storm_min_rogue_sigs = int(min_rogue_sigs)

    # -- per-family perf plumbing ------------------------------------------
    def _fam(self, family: str) -> _Family:
        # callers hold self._lock
        f = self._fams.get(family)
        if f is None:
            f = self._fams[family] = _Family()
            self.perf.add_u64_counter(
                f"compile_{family}_total", f"{family} compiles")
            self.perf.add_histogram(
                f"exec_{family}_us",
                f"{family} dispatch wall per cache-hit call (us)")
        return f

    def _record(self, kind: str, family: str, detail: str,
                level: int = 10) -> None:
        # callers hold self._lock; the gather-ring write happens
        # outside would double-lock Log — Log has its own lock and is
        # reentrancy-safe relative to ours (we never call back)
        self._events.append((time.monotonic(), kind, family, detail))
        log = self._log
        if log is not None:
            log.log("tpu", level, f"devwatch {kind} {family}: {detail}")

    # -- compile lifecycle (the instrumented_jit wrapper) ------------------
    def compile_begin(self, family: str) -> int:
        t0 = time.monotonic()
        with self._lock:
            self._live_seq += 1
            tok = self._live_seq
            # snapshot the persist-hit count: a delta over this
            # compile's span attributes the on-disk cache hit to the
            # family (the jax monitoring event itself is unlabeled)
            self._live[tok] = (family, t0, self._persist_hits)
        return tok

    def compile_end(self, token: int, sig: Tuple,
                    error: bool = False) -> None:
        t1 = time.monotonic()
        # classify against the declared shape-bucket ABI outside the
        # lock (pure registry lookup; lazy import breaks the cycle —
        # shapebucket imports this module at top level)
        from ceph_tpu.tpu import shapebucket

        with self._lock:
            family, t0, persist0 = self._live.pop(token, ("?", t1, 0))
            self._spans.append((t0, t1))
            self.last_compile_end = t1
            if error:
                self._record("compile", family,
                             f"FAILED sig=({sig_str(sig)})", level=1)
                return
            wall = t1 - t0
            fam = self._fam(family)
            fam.compiles += 1
            fam.compile_s += wall
            fam.sigs[sig] = fam.sigs.get(sig, 0) + 1
            self.perf.inc("compile_total")
            self.perf.inc(f"compile_{family}_total")
            self.perf.tinc("compile_seconds", wall)
            self.perf.set("distinct_shapes",
                          sum(len(f.sigs) for f in self._fams.values()))
            declared = shapebucket.sig_declared(family, sig)
            if not declared:
                klass = "rogue"
                fam.rogue += 1
                self.perf.inc("rogue_compiles")
            elif self._warmup > 0:
                klass = "warmup"
                fam.warmup += 1
                self.perf.inc("warmup_compiles")
            else:
                klass = "bucketed-cold"
                fam.cold += 1
            persist_d = self._persist_hits - persist0
            if persist_d > 0:
                fam.persist_hits += persist_d
            # warmup-classified compiles never feed the storm window:
            # a DeviceWarmup pass walks the whole declared ladder by
            # design, and the detector no longer has to heuristically
            # tolerate that burst (rogues are rogue even during
            # warmup, so they still count)
            if klass != "warmup":
                self._recent.append((t1, family, sig, not declared))
            self._record(
                "compile", family,
                f"[{klass}] sig=({sig_str(sig)}) wall_ms="
                f"{wall * 1e3:.1f}"
                + (" persist-hit" if persist_d > 0 else ""),
                level=1 if klass == "rogue" else 10)
            if self._steady > 0:
                GUARD_VIOLATIONS.append(
                    f"XLA compile inside a steady-state section: "
                    f"family={family} class={klass} "
                    f"sig=({sig_str(sig)}) "
                    f"wall_ms={wall * 1e3:.1f} — warm this shape up "
                    "front or pad it into an already-compiled bucket")
            storm = self._check_storm(family, t1)
        if storm is not None:
            self._warn_storm(storm)

    def note_persist(self, hit: bool) -> None:
        """One persistent-compilation-cache event (jax monitoring
        listener): a hit means THIS process skipped a compile some
        previous process already paid for — the cross-process half of
        killing the compile wall."""
        with self._lock:
            if hit:
                self._persist_hits += 1
                self.perf.inc("cache_persist_hits")
            else:
                self._persist_misses += 1
                self.perf.inc("cache_persist_misses")

    def persist_totals(self) -> Tuple[int, int]:
        with self._lock:
            return self._persist_hits, self._persist_misses

    @contextlib.contextmanager
    def warmup_scope(self):
        """Mark compiles as warmup (declared-bucket compiles paid up
        front by a DeviceWarmup pass, not charged as cold misses)."""
        with self._lock:
            self._warmup += 1
        try:
            yield self
        finally:
            with self._lock:
                self._warmup -= 1

    def note_hit(self, family: str, dur_s: float) -> None:
        with self._lock:
            fam = self._fam(family)
            fam.hits += 1
            fam.dispatches += 1
            self.perf.inc("cache_hits")
            self.perf.hinc(f"exec_{family}_us", dur_s * 1e6)

    def note_wait(self, dur_s: float) -> None:
        with self._lock:
            self.wait_s += dur_s

    def note_trace(self, family: str) -> None:
        """A pallas_call construction ran — trace(-re)entry evidence
        for the kernel family (the jit wrapper around it carries the
        compile timing; this counts how often XLA re-walked the
        kernel body)."""
        with self._lock:
            self._fam(family).traces += 1

    def note_batch(self, kind: str, jobs: int, shapes: List[Tuple],
                   dur_s: float) -> None:
        """One StripeBatchQueue dispatch — the flight recorder's
        batch-level event (ring + gather log, bounded: one per
        coalesced batch)."""
        with self._lock:
            self._record(
                "batch", "queue",
                f"kind={kind} jobs={jobs} shapes={shapes} "
                f"dur_ms={dur_s * 1e3:.1f}", level=15)

    # -- storm detection ---------------------------------------------------
    def _check_storm(self, family: str,
                     now: float) -> Optional[Dict[str, Any]]:
        # callers hold self._lock.  Two thresholds over the same
        # window: ROGUE (undeclared) signatures trip at the tight
        # count — undeclared churn is a bug regardless of volume —
        # while declared signatures keep the loose ROUND10-calibrated
        # total (a declared cold ladder is finite by construction and
        # a warmup pass walks it fast).
        horizon = now - self.storm_window_s
        recent = [(s, r) for (t, f, s, r) in self._recent
                  if f == family and t >= horizon]
        distinct = list(dict.fromkeys(s for s, _r in recent))
        rogue_distinct = list(dict.fromkeys(
            s for s, r in recent if r))
        if len(rogue_distinct) >= self.storm_min_rogue_sigs:
            kind, storm_sigs = "rogue", rogue_distinct
        elif len(distinct) >= self.storm_min_sigs:
            kind, storm_sigs = "declared", distinct
        else:
            return None
        last = self._storm_last.get(family, 0.0)
        if now - last < self.storm_window_s:
            return None  # one WARN per family per window
        self._storm_last[family] = now
        dim = _churn_dim(storm_sigs)
        storm = {
            "family": family,
            "kind": kind,
            "distinct_signatures": len(storm_sigs),
            "rogue_signatures": len(rogue_distinct),
            "window_s": self.storm_window_s,
            "churning": dim,
            "signatures": [sig_str(s)
                           for s in storm_sigs[-_SIGS_KEPT:]],
            "at": time.time(),
        }
        self.storms.append(storm)
        del self.storms[:-16]
        self.perf.inc("recompile_storms")
        self._record("storm", family,
                     f"[{kind}] {len(storm_sigs)} distinct sigs in "
                     f"{self.storm_window_s:.0f}s, churning {dim}",
                     level=1)
        return storm

    def _warn_storm(self, storm: Dict[str, Any]) -> None:
        # outside self._lock: the cluster callback may take arbitrary
        # locks (mon session)
        log = self._log
        what = ("undeclared (rogue) shape signatures"
                if storm.get("kind") == "rogue"
                else "distinct shape signatures")
        msg = (f"RECOMPILE_STORM: kernel family "
               f"'{storm['family']}' compiled "
               f"{storm['distinct_signatures']} {what} "
               f"within {storm['window_s']:.0f}s "
               f"(churning dimension: {storm['churning']}) — pad the "
               "churning dimension to a declared bucket "
               "(shapebucket.covering, the PR 3 CRUSH fix as the "
               "repo-wide shape ABI)")
        if log is not None:
            log.cluster("WRN", msg)

    # -- steady-state guard ------------------------------------------------
    @contextlib.contextmanager
    def steady_state(self):
        """Declare "warmup is done": any compile inside this section
        is a bug (recorded in GUARD_VIOLATIONS; the tier-1 conftest
        fails the test, the bench reports it)."""
        with self._lock:
            self._steady += 1
        try:
            yield self
        finally:
            with self._lock:
                self._steady -= 1

    # -- queries -----------------------------------------------------------
    def compile_activity_since(self, t0: float) -> bool:
        """Cheap lock-free pre-check for the hot blame loop: False
        means no compile is live and none FINISHED after ``t0``, so
        no overlap query over [t0, now] can return nonzero.  Benign
        races read one stale stamp and cost at most one full check."""
        return bool(self._live) or self.last_compile_end > t0

    def compile_overlap_s(self, t0: float, t1: float) -> float:
        """Seconds of [t0, t1] (monotonic) overlapped by any compile —
        finished spans and still-live compiles both count.  The
        op-level blame primitive: an encode batch whose wait window
        overlaps a compile was stalled BY that compile (one device
        worker, one compiler lock)."""
        if t1 <= t0:
            return 0.0
        total = 0.0
        now = time.monotonic()
        with self._lock:
            spans = list(self._spans)
            spans += [(s0, now) for (_f, s0, _p) in self._live.values()]
        for s0, s1 in spans:
            lo, hi = max(t0, s0), min(t1, s1)
            if hi > lo:
                total += hi - lo
        return min(total, t1 - t0)

    def compile_totals(self) -> Dict[str, float]:
        """Cumulative compile totals — the bench's per-phase delta
        source for the compile-vs-steady split (now including the
        shape-ABI classification and persistent-cache hits)."""
        with self._lock:
            return {
                "compiles": sum(f.compiles for f in self._fams.values()),
                "compile_seconds": round(
                    sum(f.compile_s for f in self._fams.values()), 6),
                "rogue": sum(f.rogue for f in self._fams.values()),
                "warmup": sum(f.warmup for f in self._fams.values()),
                "persist_hits": self._persist_hits,
            }

    def family_stats(self, family: str) -> Dict[str, Any]:
        with self._lock:
            f = self._fams.get(family)
            if f is None:
                return {"compiles": 0, "compile_s": 0.0,
                        "distinct_signatures": 0, "cache_hits": 0,
                        "dispatches": 0, "traces": 0,
                        "warmup": 0, "cold": 0, "rogue": 0,
                        "persist_hits": 0}
            return {"compiles": f.compiles,
                    "compile_s": round(f.compile_s, 6),
                    "distinct_signatures": len(f.sigs),
                    "cache_hits": f.hits, "dispatches": f.dispatches,
                    "traces": f.traces,
                    "warmup": f.warmup, "cold": f.cold,
                    "rogue": f.rogue,
                    "persist_hits": f.persist_hits}

    def dump(self) -> Dict[str, Any]:
        """The ``device compile dump`` payload: the per-family compile
        table, recent storms, live compiles, and the event-ring tail."""
        now = time.monotonic()
        with self._lock:
            fams = {}
            for name, f in sorted(self._fams.items()):
                fams[name] = {
                    "compiles": f.compiles,
                    "compile_s": round(f.compile_s, 6),
                    "distinct_signatures": len(f.sigs),
                    "cache_hits": f.hits,
                    "dispatches": f.dispatches,
                    "traces": f.traces,
                    "warmup": f.warmup,
                    "cold": f.cold,
                    "rogue": f.rogue,
                    "persist_hits": f.persist_hits,
                    "signatures": [
                        {"sig": sig_str(s), "compiles": n}
                        for s, n in list(f.sigs.items())[-_SIGS_KEPT:]],
                }
            live = [{"family": fam, "age_s": round(now - t0, 3)}
                    for fam, t0, _p in self._live.values()]
            events = [
                {"age_s": round(now - t, 3), "kind": k,
                 "family": fam, "detail": d}
                for t, k, fam, d in list(self._events)[-50:]]
            return {
                "families": fams,
                "totals": {
                    "compiles": sum(x.compiles
                                    for x in self._fams.values()),
                    "compile_seconds": round(
                        sum(x.compile_s for x in self._fams.values()),
                        6),
                    "distinct_shapes": sum(
                        len(x.sigs) for x in self._fams.values()),
                    "cache_hits": sum(x.hits
                                      for x in self._fams.values()),
                    "rogue_compiles": sum(
                        x.rogue for x in self._fams.values()),
                    "warmup_compiles": sum(
                        x.warmup for x in self._fams.values()),
                    "cache_persist_hits": self._persist_hits,
                    "cache_persist_misses": self._persist_misses,
                },
                "warmup": self.warmup_stats,
                "compile_cache_dir": _cache_dir_for_dump(),
                "storms": list(self.storms),
                "live_compiles": live,
                "recent_events": events,
            }

    def device_state(self) -> Dict[str, Any]:
        """The crash-report device section: what the device runtime
        was doing when the process died — queue depth, staging-pool
        occupancy, the in-flight batch, live compiles, and the last
        compile events (the signal_handler.cc recent-ring role)."""
        now = time.monotonic()
        out: Dict[str, Any] = {}
        q = self._queue
        if q is None:
            try:
                from ceph_tpu.tpu.queue import default_queue

                q = default_queue()
            except Exception:  # pragma: no cover — import-cycle rig
                q = None
        if q is not None:
            try:
                out["queue_depth"] = q._q.qsize()
                out["staging_slots_used"] = q.pool.occupancy
                out["staging"] = q.stats.snapshot()
                out["in_flight_batch"] = q.inflight_batch()
            except Exception as e:  # a torn queue must not kill the
                out["queue_error"] = repr(e)  # crash report itself
        with self._lock:
            out["live_compiles"] = [
                {"family": fam, "age_s": round(now - t0, 3)}
                for fam, t0, _p in self._live.values()]
            out["last_compiles"] = [
                {"age_s": round(now - t, 3), "family": fam,
                 "detail": d}
                for t, k, fam, d in list(self._events)
                if k == "compile"][-10:]
            out["storms"] = list(self.storms)
        return out

    # -- Prometheus (family-labeled cluster metrics) -----------------------
    def export_prometheus(self, lines: List[str]) -> None:
        """Family-labeled ``ceph_xla_*`` exposition lines (the mgr
        PrometheusModule appends them to its cluster section).
        Histograms follow PR 9's rule: cumulative finite le buckets
        plus the mandatory terminal ``le="+Inf"`` equal to _count."""
        with self._lock:
            fams = sorted(self._fams.items())
            if not fams:
                return
            rows = [(name, f.compiles, round(f.compile_s, 6),
                     len(f.sigs), f.hits, f.rogue, f.persist_hits)
                    for name, f in fams]
        for metric, idx, typ in (
                ("ceph_xla_compile_total", 1, "counter"),
                ("ceph_xla_compile_seconds", 2, "counter"),
                ("ceph_xla_distinct_shapes", 3, "gauge"),
                ("ceph_xla_cache_hits", 4, "counter"),
                ("ceph_xla_rogue_compiles", 5, "counter"),
                ("ceph_xla_cache_persist_hits", 6, "counter")):
            lines.append(f"# TYPE {metric} {typ}")
            for row in rows:
                lines.append(
                    f'{metric}{{family="{row[0]}"}} {row[idx]}')
        hists = self.perf.dump()
        lines.append("# TYPE ceph_xla_exec_us histogram")
        for name, *_rest in rows:
            val = hists.get(f"exec_{name}_us")
            if not isinstance(val, dict):
                continue
            label = f'family="{name}"'
            acc = 0
            for i, b in enumerate(val.get("buckets", [])):
                acc += b
                lines.append(
                    f'ceph_xla_exec_us_bucket{{{label},'
                    f'le="{1 << i}"}} {acc}')
            lines.append(
                f'ceph_xla_exec_us_bucket{{{label},le="+Inf"}} '
                f'{val["count"]}')
            lines.append(
                f'ceph_xla_exec_us_count{{{label}}} {val["count"]}')
            lines.append(
                f'ceph_xla_exec_us_sum{{{label}}} {val["sum"]}')


def _cache_dir_for_dump() -> Optional[str]:
    try:
        from ceph_tpu.tpu import shapebucket

        return shapebucket.compile_cache_dir()
    except Exception:  # pragma: no cover — torn import rig
        return None


_WATCH = DeviceWatch()


def watch() -> DeviceWatch:
    """The process-wide watcher (the default_queue() shape: one
    device runtime per process, one watcher)."""
    return _WATCH


# ---------------------------------------------------------------------------
# The sanctioned jit / pallas entry points (cephlint no-unwatched-jit
# forbids direct jax.jit / pl.pallas_call everywhere else in ceph_tpu).
# ---------------------------------------------------------------------------

def instrumented_jit(fun: Optional[Callable] = None, *,
                     family: str, **jit_kwargs) -> Callable:
    """``jax.jit`` with compile/dispatch attribution, for every family
    at once: the traced function runs under ``jax.named_scope(family)``
    (device ops carry the family in their op metadata and the trace's
    name-scope line; the program itself and its compile-cache key are
    what they were), and every call is a ``dev.dispatch`` span (upload
    of numpy operands and enqueue; the device's work is awaited in
    :func:`fetch`).

    Usable directly (``instrumented_jit(run, family="gf256_swar",
    donate_argnums=(0,))``) or as a decorator via ``functools.partial``
    — both shapes appear at the adopted call sites.  The wrapper keeps
    its OWN seen-signature set (one per jit'd function, mirroring
    jax's per-function compile cache): a call with a novel signature
    is timed as a compile (trace + compile + first execute — the wall
    the op actually waited), a seen signature is a cache hit timed
    into the family's execute histogram.
    """
    if fun is None:
        return functools.partial(instrumented_jit, family=family,
                                 **jit_kwargs)
    import jax

    @functools.wraps(fun)
    def scoped(*args, **kwargs):
        with jax.named_scope(family):
            return fun(*args, **kwargs)

    jitted = jax.jit(scoped, **jit_kwargs)
    seen: set = set()
    # static args key by VALUE (a distinct static value is a distinct
    # compile in jax); everything else by shape/dtype/type
    stat_nums = jit_kwargs.get("static_argnums")
    stat_nums = ((stat_nums,) if isinstance(stat_nums, int)
                 else tuple(stat_nums or ()))  # jax accepts a bare int
    stat_names = jit_kwargs.get("static_argnames")
    stat_names = ((stat_names,) if isinstance(stat_names, str)
                  else tuple(stat_names or ()))

    @functools.wraps(fun)
    def wrapper(*args, **kwargs):
        sig = signature(args, kwargs, stat_nums, stat_names)
        w = _WATCH
        if sig in seen:
            with tracing.span("dev.dispatch", family=family) as sp:
                out = jitted(*args, **kwargs)
            w.note_hit(family, sp.seconds)
            return out
        tok = w.compile_begin(family)
        failed = True
        try:
            with tracing.span("dev.dispatch", family=family):
                out = jitted(*args, **kwargs)
            failed = False
        finally:
            w.compile_end(tok, sig, error=failed)
        seen.add(sig)
        return out

    wrapper.devwatch_family = family
    # the underlying jax.jit object, for ahead-of-time lowering
    # (tests/test_chip_compile.py compiles for a described chip)
    wrapper.jitted = jitted
    return wrapper


def fetch(out):
    """A device result as a host array: the ONE place the host blocks
    until the device is done (the sanctioned batched d2h of the encode,
    decode and crc paths), so the one site of the ``dev.wait`` span.
    The time feeds :attr:`DeviceWatch.wait_s`."""
    with tracing.span("dev.wait") as sp:
        # cephlint: disable=no-d2h-on-hot-path — the engine's own
        # batched fetch; callers say why theirs is sanctioned
        host = np.asarray(out)
    _WATCH.note_wait(sp.seconds)
    return host


def instrumented_pallas_call(kernel: Callable, *, family: str,
                             **kwargs):
    """``pl.pallas_call`` with trace-re-entry attribution: every
    construction (= XLA walking the kernel body again) increments the
    family's ``traces`` counter; the compile wall itself is carried by
    the ``instrumented_jit`` wrapper enclosing the call."""
    from jax.experimental import pallas as pl

    _WATCH.note_trace(family)
    return pl.pallas_call(kernel, **kwargs)
