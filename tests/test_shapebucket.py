"""Shape-bucket ABI (PR 17): covering buckets, declared-vs-rogue
compile classification, boot-time DeviceWarmup (budgeted + resumable),
bucketed-dispatch bit-exactness, and the tightened rogue storm
threshold.

The persistent-compile-cache cross-process acceptance lives at the
bottom behind the slow tier (it boots a second interpreter)."""

import os
import subprocess
import sys

import numpy as np
import pytest

from ceph_tpu.tpu import devwatch, shapebucket
from ceph_tpu.tpu.devwatch import GUARD_VIOLATIONS, instrumented_jit, \
    signature, watch
from ceph_tpu.tpu.shapebucket import (
    BucketSpec, DeviceWarmup, covering, odd_part, round_up_pow2,
)

from tests.test_devwatch import StubLog, dw  # noqa: F401 — fixture


@pytest.fixture
def fam_registry():
    """Temporarily extend the family registry; restore on exit."""
    saved = dict(shapebucket._REGISTRY)
    yield shapebucket._REGISTRY
    shapebucket._REGISTRY.clear()
    shapebucket._REGISTRY.update(saved)


def _codec(profile="plugin=isa k=2 m=1 technique=reed_sol_van"):
    from ceph_tpu.ec import codec_from_profile

    return codec_from_profile(profile)


# -- covering bucket math ----------------------------------------------------

def test_covering_properties():
    assert round_up_pow2(1) == 1
    assert round_up_pow2(5) == 8
    assert odd_part(0) == 0
    assert odd_part(96) == 3
    for n in (1, 2, 3, 63, 64, 65, 1000, 4096, 4097, 99999):
        for gran in (1, 3, 8):
            c = covering(n, gran)
            assert c >= n and c % gran == 0
            assert c == covering(c, gran), "covering must be idempotent"
            # the output is a declared ladder rung of any default spec
            assert BucketSpec("x").dim_declared(c) or c > (1 << 26)
    # floor shares one bucket across tiny batches
    assert covering(3, 1, floor=64) == 64
    # gran carries array-codec column granularity
    assert covering(4097, 8) == 8 * 1024


def test_sig_declared_grammar(fam_registry):
    shapebucket.declare("t_gram", free_args=(1,))
    ok = signature((np.zeros((2, 4096), np.uint8),), {})
    assert shapebucket.sig_declared("t_gram", ok)
    # small static geometry always declared
    assert shapebucket.sig_declared(
        "t_gram", signature((np.zeros((8, 64), np.uint8),), {}))
    # arbitrary unpadded width: large odd part -> rogue
    rogue = signature((np.zeros((2, 4097), np.uint8),), {})
    assert not shapebucket.sig_declared("t_gram", rogue)
    # free_args positions are map-scoped: any dim passes there
    free = signature((np.zeros(128, np.int32),
                      np.zeros(1237, np.uint32)), {})
    assert shapebucket.sig_declared("t_gram", free)
    # ...but only at the declared position
    swapped = signature((np.zeros(1237, np.uint32),), {})
    assert not shapebucket.sig_declared("t_gram", swapped)
    # unknown family: NO declared surface
    assert not shapebucket.sig_declared("t_unknown_fam", ok)


def test_every_queue_bucket_is_declared():
    """The buckets the dispatch sites actually produce must all be
    inside their family's declared surface (the ABI's consistency)."""
    spec = shapebucket.get_spec("gf256_swar")
    for n in range(1, 300000, 7919):
        for gran in (1, 2, 8):
            assert spec.dim_declared(covering(n, gran))


# -- devwatch classification -------------------------------------------------

def test_compile_classification_warmup_cold_rogue(dw, fam_registry):  # noqa: F811
    shapebucket.declare("t_klass")
    f = instrumented_jit(lambda x: x * 2, family="t_klass")
    base = dw.family_stats("t_klass")
    with dw.warmup_scope():
        f(np.zeros(128, np.int32))   # declared bucket, inside warmup
    f(np.zeros(256, np.int32))       # declared bucket, cold hit
    f(np.zeros(257, np.int32))       # 257 = odd>63: undeclared
    st = dw.family_stats("t_klass")
    assert st["warmup"] - base["warmup"] == 1
    assert st["cold"] - base["cold"] == 1
    assert st["rogue"] - base["rogue"] == 1
    assert dw.perf.value("rogue_compiles") >= 1
    tot = dw.compile_totals()
    assert {"compiles", "compile_seconds", "rogue", "warmup",
            "persist_hits"} <= set(tot)
    fams = dw.dump()["families"]["t_klass"]
    assert fams["rogue"] == st["rogue"]


def test_steady_guard_names_the_class(dw, fam_registry):  # noqa: F811
    shapebucket.declare("t_guard_cls")
    f = instrumented_jit(lambda x: x + 1, family="t_guard_cls")
    with dw.steady_state():
        f(np.zeros(515, np.int32))  # rogue AND in-steady
    assert len(GUARD_VIOLATIONS) == 1
    assert "class=rogue" in GUARD_VIOLATIONS[0]
    GUARD_VIOLATIONS.clear()


# -- storm thresholds: rogue trips tight, declared ladders don't -------------

def test_rogue_storm_trips_at_tight_threshold(dw):  # noqa: F811
    log = StubLog()
    dw.attach_log(log)
    # defaults: rogue threshold 3, declared threshold 8
    f = instrumented_jit(lambda x: x - 1, family="t_rogue_storm")
    for n in (70, 74, 78):  # undeclared family: every sig is rogue
        f(np.zeros(n, np.int32))
    warns = [m for _l, m in log.cluster_msgs if "RECOMPILE_STORM" in m]
    assert warns and "undeclared (rogue)" in warns[0]
    storm = dw.dump()["storms"][-1]
    assert storm["family"] == "t_rogue_storm"
    assert storm["kind"] == "rogue"
    assert storm["rogue_signatures"] == 3


def test_declared_cold_ladder_is_not_a_storm(dw, fam_registry):  # noqa: F811
    shapebucket.declare("t_ladder")
    log = StubLog()
    dw.attach_log(log)
    f = instrumented_jit(lambda x: x ^ 3, family="t_ladder")
    for n in (128, 256, 512, 1024):  # a declared warmup ladder
        f(np.zeros(n, np.int32))
    assert not [m for _l, m in log.cluster_msgs if "t_ladder" in m]


# -- DeviceWarmup: budget, resume, steady-state handoff ----------------------

def test_warmup_budget_exhaustion_resumes_on_demand(dw):  # noqa: F811
    w = DeviceWarmup(_codec(), cols=(4096,))
    st = w.run(budget_s=0.0)  # budget gone before the first item
    assert st["pending"] > 0 and not st["done"]
    assert any("(budget)" in s for s in st["skipped"])
    st2 = w.run(budget_s=60.0)  # the admin-command resume
    assert st2["done"] and st2["pending"] == 0
    assert st2["runs"] == 2
    assert "crc32c_device" in st2["families_warmed"]
    assert watch().warmup_stats["done"]


def test_warmup_codec_items_wait_for_provider(dw):  # noqa: F811
    """The OSD-at-init shape: no osdmap -> no codec; codec items stay
    pending (not errors) and complete once the provider yields one."""
    holder = {"codec": None}
    w = DeviceWarmup(codec_fn=lambda: holder["codec"], cols=(4096,))
    st = w.run(budget_s=60.0)
    assert st["pending"] > 0 and not st["done"]
    assert any("not ready" in s for s in st["skipped"])
    holder["codec"] = _codec()
    st2 = w.run(budget_s=60.0)
    assert st2["done"], st2
    assert any(s.startswith("gf256") for s in st2["warmed"])


def test_warmed_write_path_is_steady(dw):  # noqa: F811
    """After a DeviceWarmup pass, encode + fused-crc + decode batches
    at a warmed bucket run with the steady-state guard armed and zero
    violations — the bench acceptance in miniature."""
    from ceph_tpu.tpu.queue import StripeBatchQueue

    codec = _codec()
    w = DeviceWarmup(codec, cols=(4096,))
    st = w.run(budget_s=120.0)
    assert st["done"], st
    q = StripeBatchQueue()
    try:
        rng = np.random.default_rng(7)
        planes = rng.integers(0, 256, (2, 4096), np.uint8)
        with dw.steady_state():
            q.encode(codec, planes)
            q.encode_crc_async(codec, planes, size=8192).result(30.0)
            coding = q.encode(codec, planes)
            avail = {1: planes[1], 2: coding[0]}
            q.decode_data(codec, avail)
        assert not GUARD_VIOLATIONS, GUARD_VIOLATIONS
    finally:
        q.stop()


# -- bucketed dispatch is bit-identical --------------------------------------

def test_bucketed_batch_bit_identical_to_unpadded():
    """Golden compare: covering-padded dispatch through the queue ==
    direct unpadded computation, for encode, fused crc, and decode, at
    deliberately odd widths (the widths the pad exists for)."""
    from ceph_tpu.core.crc import crc32c
    from ceph_tpu.tpu.queue import StripeBatchQueue

    codec = _codec()
    rng = np.random.default_rng(17)
    q = StripeBatchQueue()
    try:
        for width in (100, 1337, 5000):
            planes = rng.integers(0, 256, (codec.k, width), np.uint8)
            want = np.asarray(codec.encode_array(planes.copy()))
            got = q.encode(codec, planes)
            assert got.shape == want.shape
            assert np.array_equal(got, want), f"width={width}"
            # fused crc path: digests must equal host crc of each shard
            coding2, crcs = q.encode_crc_async(
                codec, planes, size=planes.nbytes).result(30.0)
            assert np.array_equal(coding2, want)
            shards = np.concatenate([planes, want], axis=0)
            host = [crc32c(bytes(shards[s])) for s in
                    range(codec.k + codec.m)]
            assert list(map(int, crcs)) == host, f"width={width}"
            # decode: drop shard 0, recover from survivors
            avail = {1: planes[1], codec.k: want[0]}
            data = q.decode_data(codec, avail)
            assert np.array_equal(data, planes), f"width={width}"
    finally:
        q.stop()


# -- vstart boot warmup: zero storms, steady cluster ops ---------------------

def test_vstart_boot_warmup_no_storms_and_steady_ops(dw, tmp_path):  # noqa: F811
    """Regression for the storm-detector hardening: a full boot warmup
    (vstart warmup=True, EC pool) raises ZERO recompile-storm WARNs,
    and post-warmup cluster write/read runs under the steady-state
    guard without violations."""
    from ceph_tpu.vstart import VStartCluster

    log = StubLog()
    dw.attach_log(log)
    storms0 = len(dw.dump()["storms"])
    with VStartCluster(n_mons=1, n_osds=3, warmup=True,
                       conf={"tpu_warmup_budget_s": 120.0}) as c:
        pool = c.create_pool("wb", size=3, pool_type="erasure",
                             ec_profile="plugin=isa k=2 m=1 "
                                        "technique=reed_sol_van")
        for o in c.osds.values():
            assert o._warmup is not None, "boot warmup never ran"
        io = c.client().ioctx(pool)
        payload = bytes(range(256)) * 32  # 8 KiB
        with dw.steady_state():
            io.write_full("warmed", payload)
            assert io.read("warmed") == payload
        assert not GUARD_VIOLATIONS, GUARD_VIOLATIONS
    assert len(dw.dump()["storms"]) == storms0, dw.dump()["storms"]
    warns = [m for _l, m in log.cluster_msgs if "RECOMPILE_STORM" in m]
    assert not warns, warns


# -- persistent compile cache ------------------------------------------------

def test_setup_compile_cache_idempotent(tmp_path):
    d = str(tmp_path / "xc")
    assert shapebucket.setup_compile_cache(d)
    assert shapebucket.compile_cache_dir() == d
    assert shapebucket.setup_compile_cache(d)  # second call: no-op
    assert not shapebucket.setup_compile_cache("")  # empty disables


def test_compile_cache_dir_resolver():
    """Placed from outside: the variable where set, else the fixed
    path inside the checkout (a path that moves never hits)."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert shapebucket.resolve_compile_cache_dir({}) == (
        os.path.join(repo, ".jax_cache"), True)
    assert shapebucket.resolve_compile_cache_dir(
        {"JAX_COMPILATION_CACHE_DIR": "/x/y"}) == ("/x/y", False)
    assert shapebucket.resolve_compile_cache_dir(
        {"JAX_COMPILATION_CACHE_DIR": ""})[1] is True


@pytest.mark.parametrize("from_env", [True, False])
def test_setup_compile_cache_sets_no_dir_when_env_is_set(
        monkeypatch, tmp_path, from_env):
    """Where JAX_COMPILATION_CACHE_DIR is set, jax's own reading of it
    stands: no code sets another directory."""
    import jax

    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.append((k, v)))
    monkeypatch.setattr(shapebucket, "_cache_dir", None)
    if from_env:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                           str(tmp_path / "env"))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert shapebucket.setup_compile_cache()
    dirs = [v for k, v in updates if k == "jax_compilation_cache_dir"]
    if from_env:
        assert dirs == []
        assert shapebucket.compile_cache_dir() == str(tmp_path / "env")
    else:
        assert dirs == [shapebucket.compile_cache_dir()]
        assert dirs[0].endswith(".jax_cache")


_CHILD = r"""
import os, sys
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import numpy as np
from ceph_tpu.tpu import devwatch, shapebucket

shapebucket.setup_compile_cache(sys.argv[1])
f = devwatch.instrumented_jit(lambda x: (x * 3) ^ 7,
                              family="gf256_swar")
f(np.zeros((2, 4096), np.uint8))
h, m = devwatch.watch().persist_totals()
print("PERSIST", h, m)
"""


@pytest.mark.slow
def test_persistent_cache_spans_processes(tmp_path):
    """Acceptance: a SECOND process pointed at the same cache dir pays
    zero compile wall — its compile is served from disk
    (cache_persist_hits > 0), proving restart/failover skip the wall."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.dirname(os.path.dirname(
                   os.path.abspath(__file__))))
    cache = str(tmp_path / "xla_cache")

    def run():
        out = subprocess.run(
            [sys.executable, "-c", _CHILD, cache], env=env,
            capture_output=True, text=True, timeout=240)
        assert out.returncode == 0, out.stderr
        line = [ln for ln in out.stdout.splitlines()
                if ln.startswith("PERSIST")][-1]
        _tag, hits, misses = line.split()
        return int(hits), int(misses)

    hits1, misses1 = run()   # cold process: populates the cache
    assert misses1 >= 1 and hits1 == 0
    assert os.listdir(cache), "nothing persisted"
    hits2, _m2 = run()       # warm process: reads it back
    assert hits2 >= 1, "second process re-paid the compile wall"
