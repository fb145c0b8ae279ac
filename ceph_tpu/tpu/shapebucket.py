"""Shape-bucket ABI — the declared compile surface of every kernel family.

PR 10 measured the wall (89% of a representative workload inside XLA
compiles, 27.7s in CRUSH mapper programs alone) and PR 8 blamed ~40%
of write p50 on compile-contaminated encode-queue wait.  The fix is
the standard one from the XLA systems literature: make the set of
shapes a kernel family can be asked to compile FINITE and DECLARED,
so that

- every dispatch site pads its batch up to a covering bucket
  (:func:`covering` — the PR 3 CRUSH pow2 high-water fix promoted
  from a local idiom to the repo-wide discipline),
- devwatch can classify every observed compile as ``warmup``
  (declared bucket, compiled inside a :class:`DeviceWarmup` pass),
  ``bucketed-cold`` (declared but first hit outside warmup), or
  ``rogue`` (UNDECLARED signature — a bug by definition: counted,
  WARN'd, and asserted zero by the steady-state guard),
- a :class:`DeviceWarmup` pass at daemon boot compiles each family
  against its declared buckets BEFORE the daemon answers ops, bounded
  by ``tpu_warmup_budget_s`` and resumable on demand
  (``ceph daemon osd.N device warmup``), and
- a persistent on-disk XLA compilation cache
  (:func:`setup_compile_cache`; ``JAX_COMPILATION_CACHE_DIR`` or a
  fixed ``<repo>/.jax_cache``) makes
  a SECOND process pay ~zero compile wall for any family a previous
  process warmed — restart/failover/backfill never re-pay the wall.

Bucket grammar.  A declared array dimension is either

- **static geometry** (``dim <= small_max``): k/m/R code geometry,
  the 128-lane axis, survivor counts, a seed's 1 — dims that take a
  handful of values fixed by the code profile, or
- **a ladder rung**: ``dim = odd * 2**j`` with a SMALL odd part
  (``odd_part(dim) <= odd_max``) below the family ceiling.  This is
  exactly what :func:`covering` produces — ``gran * pow2`` for the
  codec column granularity ``gran`` (1 for flat RS codecs, the
  sub-chunk count for array codecs like clay) — and what unpadded
  churn almost never produces (the density of ladder values near N is
  ~``odd_max/2 * log2(N) / N``; the PR 3 storm's arbitrary bad-set
  sizes were rogue under this grammar).

Families may exempt argument positions whose dims are legitimately
map-scoped statics (``free_args`` — the CRUSH mapper's device-weight
vector is sized by the OSD count of the map epoch, not by the call).

The cephlint ``shape-bucket-discipline`` check (never baselineable)
enforces that every ``instrumented_jit`` / ``instrumented_pallas_call``
family in ``ceph_tpu`` is declared here, and that ``tpu/queue.py``
batch dispatch goes through :func:`covering`.
"""

from __future__ import annotations

import os
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from ceph_tpu.core.lockdep import make_lock

# ---------------------------------------------------------------------------
# Covering buckets — the one padding helper every dispatch site uses
# ---------------------------------------------------------------------------


def round_up_pow2(n: int) -> int:
    """Smallest power of two >= n (1 for n <= 1)."""
    n = int(n)
    return 1 << max(0, (n - 1).bit_length())


def odd_part(n: int) -> int:
    """n with every factor of two divided out (0 -> 0)."""
    n = int(n)
    return n // (n & -n) if n else 0


def covering(n: int, gran: int = 1, floor: int = 1) -> int:
    """The covering bucket of ``n``: the smallest ``gran * 2**j`` that
    is >= both ``n`` and ``floor``.  ``gran`` carries a codec's column
    granularity (array codecs like clay need width % sub_chunk == 0);
    ``floor`` bounds the ladder from below so tiny batches share one
    bucket instead of minting log2(floor) extra shapes."""
    gran = max(1, int(gran))
    units = -(-max(int(n), 1) // gran)  # ceil
    return max(int(floor), gran * round_up_pow2(units))


# ---------------------------------------------------------------------------
# Declarations
# ---------------------------------------------------------------------------

class BucketSpec:
    """One family's declared compile surface (see module docstring)."""

    __slots__ = ("family", "small_max", "odd_max", "ceiling",
                 "free_args", "note")

    def __init__(self, family: str, *, small_max: int = 64,
                 odd_max: int = 63, ceiling: int = 1 << 26,
                 free_args: Tuple[int, ...] = (), note: str = "") -> None:
        self.family = family
        self.small_max = int(small_max)
        self.odd_max = int(odd_max)
        self.ceiling = int(ceiling)
        self.free_args = tuple(free_args)
        self.note = note

    def dim_declared(self, dim: int) -> bool:
        dim = int(dim)
        if dim <= self.small_max:
            return True
        return dim <= self.ceiling and odd_part(dim) <= self.odd_max

    def atom_declared(self, atom: Tuple, pos: int) -> bool:
        """One signature atom (devwatch._sig_of output) against this
        spec.  Non-array atoms are always declared: static values ARE
        distinct compiles by design (a matrix digest, a tile_n), and
        dynamic scalars key by type."""
        if len(atom) == 3 and atom[0] == "arr":
            if pos in self.free_args:
                return True
            shape = atom[2]
            if not isinstance(shape, tuple):
                return False  # symbolic dims: not a declared bucket
            return all(self.dim_declared(d) for d in shape)
        return True

    def sig_declared(self, sig: Tuple) -> bool:
        for pos, atom in enumerate(sig):
            if len(atom) == 2 and isinstance(atom[0], str) \
                    and isinstance(atom[1], tuple):
                # kwarg pair (name, atom)
                if not self.atom_declared(atom[1], pos):
                    return False
            elif not self.atom_declared(atom, pos):
                return False
        return True


_REGISTRY: Dict[str, BucketSpec] = {}


def declare(family: str, **kw) -> BucketSpec:
    spec = BucketSpec(family, **kw)
    _REGISTRY[family] = spec
    return spec


def get_spec(family: str) -> Optional[BucketSpec]:
    return _REGISTRY.get(family)


def declared_families() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def sig_declared(family: str, sig: Tuple) -> bool:
    """Is (family, signature) inside the declared compile surface?
    Unknown families have NO declared surface: every compile is rogue
    (the cephlint check makes an undeclared in-tree family a lint
    violation before it can become a runtime rogue)."""
    spec = _REGISTRY.get(family)
    return spec.sig_declared(sig) if spec is not None else False


# The in-tree kernel families (every devwatch-tagged family).  The
# dispatch-path padding that makes these declarations TRUE lives at
# the sites: StripeBatchQueue (covering over the column axis),
# crc32c_device (pow2 rows/cols with a 64 floor), crush/mapper.py
# (pow2 high-water fixup batches, pow2 chunks), meshio (covering over
# the stripe axis), gf256_* (fed pre-padded planes by the queue).
declare("gf256_swar",
        note="words u32[k, W]: W = cols/4, cols covering-padded by the "
             "StripeBatchQueue; k/R are code geometry")
declare("gf256_pallas",
        note="planes u32[k, T, 128]: T = cols/512 from queue-padded "
             "cols; 128-lane axis static")
declare("gf2_matmul",
        note="bit-matrix tiles: tile_n static, batch cols queue-padded")
declare("gf256_clay",
        note="the encode's one program: words u32[k, Z, W] -> "
             "u32[m, Z, W], k/m and Z = q^t static geometry of the "
             "profile, W = the per-layer byte width / 4, covering-padded "
             "to a pow2 by the StripeBatchQueue's array branch; and the "
             "pair/solve matmuls of repair and decode: rows are 1x2 pair "
             "transforms or q x kk solve matrices (static geometry), "
             "cols = (pairs or layers) * S with S that per-layer byte "
             "width — odd parts bounded by the grid constants "
             "(<= q^t <= 63 for supported profiles)")
declare("crc32c_device",
        note="(J, C) row batches: J pow2, C pow2 with 64 floor "
             "(crc32c_rows/_round_up_pow2)")
declare("crush_mapper", free_args=(1,),
        note="xs i32[n]: n pow2 (chunk or high-water fixup pad); "
             "arg1 is the device-weight vector, sized by the map "
             "epoch's OSD count (free)")
declare("meshio",
        note="stripe axis covering-padded to pow2 multiples of 4*dp")


# ---------------------------------------------------------------------------
# Persistent XLA compile cache
# ---------------------------------------------------------------------------

_cache_lock = make_lock("shapebucket.cache")
_cache_dir: Optional[str] = None
_listener_installed = False


def _on_jax_event(event: str, **kw) -> None:  # pragma: no cover - thin
    from ceph_tpu.tpu import devwatch

    if event == "/jax/compilation_cache/cache_hits":
        devwatch.watch().note_persist(hit=True)
    elif event == "/jax/compilation_cache/cache_misses":
        devwatch.watch().note_persist(hit=False)


# the one fixed place the cache lives when nobody says otherwise: a
# path inside the checkout (listed in .gitignore).  The directory is
# part of the cache key, so a path that moves (a temporary data dir)
# never hits.
_REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def resolve_compile_cache_dir(environ=os.environ) -> Tuple[str, bool]:
    """The one resolver: ``(directory, set_in_code)``.  Where
    ``JAX_COMPILATION_CACHE_DIR`` is set, jax's own reading of it
    stands and no code sets a directory; else ``<repo>/.jax_cache``."""
    env = environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env, False
    return _REPO_CACHE_DIR, True


def setup_compile_cache(path: Optional[str] = None) -> bool:
    """Turn on jax's persistent compilation cache and install the
    monitoring listener that splits on-disk cache hits
    (``cache_persist_hits`` — a compile this process never paid
    because a PREVIOUS process did) from in-process trace-cache hits.

    With no argument (OSD daemons, chip_smoke.py) the directory comes
    from :func:`resolve_compile_cache_dir`.  An explicit ``path`` is
    for tests (a ``tmp_path``); the empty string disables.
    Idempotent; returns True when the cache is live.  Thresholds are
    zeroed so every kernel persists — this repo's kernels are small
    and the wall they save is the whole point."""
    global _cache_dir, _listener_installed
    import jax

    set_in_code = True
    if path is None:
        path, set_in_code = resolve_compile_cache_dir()
    if not path:
        return False
    with _cache_lock:
        if _cache_dir == path:
            return True
        if set_in_code:
            jax.config.update("jax_compilation_cache_dir", str(path))
        jax.config.update(
            "jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update(
            "jax_persistent_cache_min_entry_size_bytes", -1)
        # jax initializes its cache object AT MOST ONCE, on the first
        # compile: any import-time jit before this call would freeze
        # the cache in its disabled (no-dir) state and the config
        # updates above would never take.  Reset so the next compile
        # re-initializes against `path`.
        from jax.experimental.compilation_cache import compilation_cache

        compilation_cache.reset_cache()
        if not _listener_installed:
            from jax import monitoring

            monitoring.register_event_listener(_on_jax_event)
            _listener_installed = True
        _cache_dir = path
        return True


def compile_cache_dir() -> Optional[str]:
    return _cache_dir


# ---------------------------------------------------------------------------
# Boot-time warmup
# ---------------------------------------------------------------------------

# default column-width ladder the warmup compiles each codec family
# against: the covering buckets of the chunk widths real pools
# produce (4k..256k objects over k in 2..8).  The queue pads every
# batch to one of these, so warming them IS warming the op path.
# 32768 is load-bearing: a 64KiB object at k=2 chunks to exactly that
# width, and an armed steady guard caught it missing.
WARM_COLS = (4096, 16384, 32768, 65536)
# the flat decode matmul takes its recovery matrix as an operand (one
# program per width serves every survivor signature), so its whole
# compile surface is finite and small: every width the queue can
# dispatch, 512 B (the narrowest Pallas row) up to its 1 Mi-column
# batch ceiling.  Degraded reads after an OSD loss then compile nothing.
WARM_DECODE_COLS = tuple(1 << j for j in range(9, 21))
# crc row-batch geometry: J coalesced jobs (pow2) x C padded columns.
# The row count the kernel sees is pow2(J) x (k+m); depth-16 client
# concurrency coalesces up to 8 jobs per batch in practice, so warm
# every pow2 rung up to there.
WARM_CRC_JOBS = (1, 2, 4, 8)


class _WarmItem:
    __slots__ = ("family", "desc", "thunk")

    def __init__(self, family: str, desc: str, thunk: Callable) -> None:
        self.family = family
        self.desc = desc
        self.thunk = thunk


class DeviceWarmup:
    """Compile the declared buckets before anyone waits on them.

    Builds a deterministic plan (smallest buckets first — partial
    budget still warms the shapes small ops hit) and executes it under
    ``watch().warmup_scope()`` so devwatch classifies the compiles as
    ``warmup``.  ``run()`` is budget-bounded and RESUMABLE: items the
    budget cut off stay pending and the next ``run()`` (the on-demand
    ``device warmup`` admin command) continues where boot stopped.
    Stats are observable via :meth:`stats` and mirrored into
    ``watch().warmup_stats`` for the ``osd.N.xla`` dump."""

    def __init__(self, codec=None, *, cols: Tuple[int, ...] = WARM_COLS,
                 codec_fn: Optional[Callable] = None,
                 crush: Optional[Callable] = None) -> None:
        # codec may be handed directly (tests, tools) or resolved at
        # RUN time via codec_fn (an OSD at init has no osdmap yet —
        # its pools, and so its codec, arrive with boot; codec items
        # stay pending until the provider yields one)
        self._codec = codec
        self._codec_fn = codec_fn
        self._crush = crush
        self._cols = tuple(sorted(int(c) for c in cols))
        self._pending: List[_WarmItem] = self._build_plan()
        self._warmed: List[str] = []
        self._skipped: List[str] = []
        self._seconds = 0.0
        self._runs = 0
        self._lock = make_lock("shapebucket.warmup")

    def _codec_now(self):
        if self._codec is not None:
            return self._codec
        if self._codec_fn is not None:
            self._codec = self._codec_fn()
        return self._codec

    # -- plan --------------------------------------------------------------
    def _build_plan(self) -> List[_WarmItem]:
        items: List[_WarmItem] = []
        for c in self._cols:
            items.append(_WarmItem(
                "crc32c_device", f"crc cols={c}",
                lambda c=c: self._warm_crc(c)))
        if self._codec is not None or self._codec_fn is not None:
            for c in self._cols:
                items.append(_WarmItem(
                    "gf256", f"encode cols~{c}",
                    lambda c=c: self._warm_encode(c)))
            for c in sorted(set(self._cols) | set(WARM_DECODE_COLS)):
                items.append(_WarmItem(
                    "gf256", f"decode cols~{c}",
                    lambda c=c: self._warm_decode(c)))
        if self._crush is not None:
            items.append(_WarmItem(
                "crush_mapper", "crush rule programs",
                self._warm_crush))
        return items

    # -- per-family warmers (False = precondition missing, retry) ----------
    def _warm_crc(self, cols: int) -> bool:
        from ceph_tpu.ops.crc32c_device import crc32c_dev, crc32c_rows

        crc32c_dev(np.zeros(cols, np.uint8))
        # the fused encp pass crcs a [k+m, batch] plane matrix: the
        # kernel's row count is pow2(jobs) * (k+m), so the warm must
        # use the REAL shard count or steady-state ops still compile
        codec = self._codec_now()
        if codec is None and self._codec_fn is not None:
            return False  # shard count unknown until the osdmap lands
        shards = (codec.k + codec.m) if codec is not None else 1
        for j in WARM_CRC_JOBS:
            full = np.zeros((shards, j * cols), np.uint8)
            offs = [i * cols for i in range(j)]
            crc32c_rows(full, offs, [cols] * j)
        return True

    def _warm_encode(self, cols: int) -> bool:
        # through encode_array so whichever engine actually serves
        # (native SWAR / XLA graph / pallas) is the one warmed
        codec = self._codec_now()
        if codec is None:
            return False
        gran = 1
        get_subs = getattr(codec, "get_sub_chunk_count", None)
        if get_subs is not None:
            gran = max(1, int(get_subs()))
        w = covering(cols, gran)
        codec.encode_array(np.zeros((codec.k, w), np.uint8))
        return True

    def _warm_decode(self, cols: int) -> bool:
        codec = self._codec_now()
        if codec is None:
            return False
        get_subs = getattr(codec, "get_sub_chunk_count", None)
        gran = max(1, int(get_subs())) if get_subs is not None else 1
        if gran > 1 and cols not in self._cols:
            return True  # the full ladder is the flat decode's
        if gran > 1 and hasattr(codec, "repair_planes"):
            # array codec (clay): warm the batched single-erasure
            # repair AND the general decode at the queue's covering
            # width (the sub-chunk-granular ladder), so steady-state
            # recovery/scrub pay zero compiles
            n = codec.k + codec.m
            w = covering(cols, gran)
            s = w // gran
            L = len(codec.repair_layers(0))
            codec.repair_planes(
                0, list(range(1, codec.d + 1)),
                np.zeros((codec.d, L, s), np.uint8))
            avail = list(range(codec.m, n))  # first m erased
            codec.decode_planes(
                avail, np.zeros((len(avail), w), np.uint8))
            return True
        if gran > 1 or getattr(codec, "recovery_matrix", None) is None:
            return True  # no flat decode matmul to warm
        n = codec.k + codec.m
        # any survivor signature warms them all: the matrix is an
        # operand of the program, not part of it
        sig = list(range(codec.m, n))[: codec.k]
        rec, _bits = codec.recovery_matrix(sig)
        from ceph_tpu.ops import gf256_swar

        # donate=True / operand=True match the queue's decode
        # dispatch — both are compile-time properties, so a warm
        # without them would leave the real path cold
        gf256_swar.gf_matmul_bytes(
            np.asarray(rec, np.uint8),
            np.zeros((codec.k, covering(cols)), np.uint8),
            donate=True, operand=True)
        return True

    def _warm_crush(self) -> bool:
        return bool(self._crush())

    # -- execution ---------------------------------------------------------
    def run(self, budget_s: float = 30.0) -> Dict[str, Any]:
        """Execute pending plan items until the budget is spent.
        Items whose preconditions are missing (no osdmap for the
        CRUSH warmer) are recorded as skipped and retried on the next
        run.  Returns :meth:`stats`."""
        from ceph_tpu.tpu import devwatch

        w = devwatch.watch()
        t0 = time.monotonic()
        budget_s = float(budget_s)
        with self._lock:
            self._runs += 1
            self._skipped = []
            pending, self._pending = self._pending, []
            with w.warmup_scope():
                for i, item in enumerate(pending):
                    if budget_s >= 0 and \
                            time.monotonic() - t0 > budget_s:
                        self._pending.extend(pending[i:])
                        self._skipped.extend(
                            f"{it.family}: {it.desc} (budget)"
                            for it in pending[i:])
                        break
                    try:
                        ok = item.thunk()
                    except Exception as e:
                        self._skipped.append(
                            f"{item.family}: {item.desc} "
                            f"(error: {e!r})")
                        continue
                    if ok:
                        self._warmed.append(
                            f"{item.family}: {item.desc}")
                    else:
                        self._pending.append(item)
                        self._skipped.append(
                            f"{item.family}: {item.desc} "
                            "(not ready)")
            self._seconds += time.monotonic() - t0
            st = self._stats_locked()
        w.warmup_stats = st
        return st

    def _stats_locked(self) -> Dict[str, Any]:
        fams = sorted({i.split(":")[0] for i in self._warmed})
        return {
            "runs": self._runs,
            "seconds": round(self._seconds, 3),
            "families_warmed": fams,
            "buckets_warmed": len(self._warmed),
            "warmed": list(self._warmed),
            "pending": len(self._pending),
            "skipped": list(self._skipped),
            "done": not self._pending,
        }

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            return self._stats_locked()
