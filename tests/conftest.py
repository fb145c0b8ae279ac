"""Test harness: 8-device virtual CPU mesh + x64, native lib autobuild.

Tests always run on the CPU backend (fast, deterministic, and
multi-device via xla_force_host_platform_device_count).  No product
entry point enables x64; the chip entry point is chip_smoke.py, and
tests/test_chip_compile.py compiles the main path's kernels for a
described v5e with x64 off.
"""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "--xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
    )

import jax

jax.config.update("jax_enable_x64", True)
# The persistent compilation cache stays ON, as in the product (every
# OSD's init calls shapebucket.setup_compile_cache(): <repo>/.jax_cache
# unless JAX_COMPILATION_CACHE_DIR is set).  A test that counts
# compiles pins it off for itself.

from ceph_tpu import _native

_native.lib()  # build csrc/ once up front

# -- runtime sanitizers (tier-1 runs with both armed) -----------------------
#
# lockdep: make_lock() hands out order-checked DMutexes for the whole
# suite, so a lock-order cycle anywhere in the msg/store/osd/mon paths
# is a deterministic LockOrderError, not a rare production hang.
# Enabled at import time — locks decide checked-vs-plain when CREATED,
# and daemons construct their locks inside tests.  CEPH_TPU_LOCKDEP=0
# opts out (e.g. when bisecting a perf regression).
#
# loop-stall: a fast-dispatched handler that holds a messenger event
# loop longer than CEPH_TPU_LOOP_STALL_MS fails the test that ran it.
# The default 1000 ms is far above any legitimate inline handler
# (microseconds) and far below the blocking bugs the contract exists
# to catch (store fsyncs, lock waits held across RPCs, 10 s dials);
# it also keeps 2-core CI scheduler hiccups from flaking tests.
import pytest

from ceph_tpu.core import lockdep

_LOCKDEP = os.environ.get("CEPH_TPU_LOCKDEP", "1") != "0"
if _LOCKDEP:
    lockdep.enable(True)
os.environ.setdefault("CEPH_TPU_LOOP_STALL_MS", "1000")

from ceph_tpu.core import optracker as _optracker
from ceph_tpu.msg import messenger as _messenger
from ceph_tpu.tpu import devwatch as _devwatch


@pytest.fixture(autouse=True)
def _sanitizers():
    if _LOCKDEP:
        lockdep.enable(True)  # re-assert: a test may have toggled it
    _messenger.LOOP_STALLS.clear()
    _optracker.LEAKS.clear()
    _devwatch.GUARD_VIOLATIONS.clear()
    yield
    stalls, _messenger.LOOP_STALLS[:] = (list(_messenger.LOOP_STALLS), [])
    if float(os.environ.get("CEPH_TPU_LOOP_STALL_MS", "0") or 0) > 0:
        assert not stalls, (
            "fast-dispatched handler(s) blocked the messenger event loop "
            "(no store work, no lock waits, no RPCs inline on the loop): "
            + "; ".join(f"{e}:{t} {s * 1e3:.0f}ms" for e, t, s in stalls))
    # TrackedOp lifecycle sanitizer: a daemon that shut down holding an
    # op whose reply went out but that never left the in-flight table
    # has a lifecycle leak (the loop-stall shape: evidence collected by
    # the machinery, asserted per test)
    leaks, _optracker.LEAKS[:] = (list(_optracker.LEAKS), [])
    assert not leaks, (
        "TrackedOp lifecycle leak(s) — replied ops must be finish()ed "
        "into history, not left in the in-flight table: "
        + "; ".join(leaks))
    # devwatch steady-state guard (the lockdep shape: machinery armed
    # for the whole suite, violations recorded only inside explicitly
    # declared steady-state sections): a test whose steady section
    # compiled a fresh XLA shape has a warmup/padding bug
    guard, _devwatch.GUARD_VIOLATIONS[:] = (
        list(_devwatch.GUARD_VIOLATIONS), [])
    assert not guard, (
        "XLA compile(s) inside a declared steady-state section: "
        + "; ".join(guard))
