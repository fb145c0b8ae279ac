"""cephlint tier-1 gate + per-check unit coverage.

The gate: the repo at HEAD must have ZERO violations beyond the
committed baseline (tools/cephlint_baseline.json).  New debt either
gets fixed, gets an inline `# cephlint: disable=<check> — why`
annotation, or is consciously accepted by regenerating the baseline —
never silently merged.

The unit tests feed each check synthetic modules with one planted bug
and one clean variant: the gate is only as good as the checks'
ability to actually catch the bug classes they claim.
"""

import os
import sys
import time

TOOLS = os.path.join(os.path.dirname(__file__), "..", "tools")
sys.path.insert(0, os.path.abspath(TOOLS))

import cephlint  # noqa: E402

from ceph_tpu.analysis import (  # noqa: E402
    ALL_CHECKS,
    SourceFile,
    discover_files,
    load_baseline,
    new_violations,
    run_checks,
)
from ceph_tpu.analysis.checks import CHECKS_BY_NAME  # noqa: E402


# -- the tier-1 gate ---------------------------------------------------------

_SCAN = {}


def _repo_scan():
    """One repo-wide scan shared by the gate tests (the parse cache
    makes re-parses free, but the checks themselves cost ~3s/pass on
    the 2-core CI box — no reason to pay it three times)."""
    if not _SCAN:
        t0 = time.perf_counter()
        files = discover_files()
        violations = run_checks(files, ALL_CHECKS)
        _SCAN.update(files=files, violations=violations,
                     elapsed=time.perf_counter() - t0)
    return _SCAN


def test_repo_has_no_new_violations():
    scan = _repo_scan()
    violations, elapsed = scan["violations"], scan["elapsed"]
    baseline = load_baseline(cephlint.DEFAULT_BASELINE)
    new = new_violations(violations, baseline)
    assert not new, (
        "new cephlint violations (fix them, annotate the line with "
        "'# cephlint: disable=<check> — why', or — for consciously "
        "accepted debt — regenerate the baseline with "
        "`python tools/cephlint.py --write-baseline`):\n" + "\n".join(
            f"  {v.path}:{v.line}: [{v.check}] {v.message}" for v in new))
    # the CI-budget contract: full suite, parse included, well under 30s
    assert elapsed < 30.0, f"lint took {elapsed:.1f}s (budget 30s)"


def test_baseline_never_grows_silently():
    """Every baseline entry must still correspond to a live violation:
    fixed debt leaves stale allowance behind, and stale allowance is
    where a regression hides.  (Regenerate the baseline after fixing.)"""
    live = {}
    for v in _repo_scan()["violations"]:
        live[v.key] = live.get(v.key, 0) + 1
    baseline = load_baseline(cephlint.DEFAULT_BASELINE)
    stale = {k: (n, live.get(k, 0)) for k, n in baseline.items()
             if live.get(k, 0) < n}
    assert not stale, (
        "baseline entries exceed live violations — debt was fixed, "
        "shrink the baseline (`python tools/cephlint.py "
        f"--write-baseline`): {stale}")


def test_cli_json_contract():
    """--json exits 0 at HEAD and emits the machine-readable shape."""
    import contextlib
    import io
    import json

    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        # one check keeps this a CLI-contract test, not a third full
        # scan (the gate itself is test_repo_has_no_new_violations)
        rc = cephlint.main(["--json", "--checks", "no-sleep-poll"])
    out = json.loads(buf.getvalue())
    assert rc == 0
    assert out["new"] == []
    assert out["files_scanned"] > 100
    assert out["checks"] == ["no-sleep-poll"]


# -- per-check unit coverage -------------------------------------------------

def _lint(tmp_path, code: str, check: str, rel: str = "ceph_tpu/fake.py"):
    p = tmp_path / "snippet.py"
    p.write_text(code)
    return [v for v in run_checks([SourceFile(str(p), rel)],
                                  [CHECKS_BY_NAME[check]])
            if v.check == check]


def test_named_locks_catches_raw_lock(tmp_path):
    bad = _lint(tmp_path, (
        "import threading\n"
        "class A:\n"
        "    def __init__(self):\n"
        "        self.lk = threading.Lock()\n"
        "        self.r = threading.RLock()\n"), "named-locks")
    assert [v.line for v in bad] == [4, 5]
    ok = _lint(tmp_path, (
        "from ceph_tpu.core.lockdep import make_lock\n"
        "lk = make_lock('x')\n"), "named-locks")
    assert not ok


def test_named_locks_inline_suppression(tmp_path):
    ok = _lint(tmp_path, (
        "import threading\n"
        "# cephlint: disable=named-locks — released cross-thread\n"
        "guard = threading.Lock()\n"), "named-locks")
    assert not ok


def test_no_sleep_poll_flags_only_short_literal_in_loop(tmp_path):
    code = (
        "import time\n"
        "def poll():\n"
        "    while True:\n"
        "        time.sleep(0.02)\n"       # flagged: the 20ms poll
        "def pace():\n"
        "    while True:\n"
        "        time.sleep(30.0)\n"       # ok: deliberate long pacing
        "def configurable(iv):\n"
        "    while True:\n"
        "        time.sleep(iv)\n"         # ok: computed interval
        "def once():\n"
        "    time.sleep(0.02)\n")          # ok: not in a loop
    bad = _lint(tmp_path, code, "no-sleep-poll")
    assert [v.line for v in bad] == [4]


def test_silent_except_flags_broad_pass_only(tmp_path):
    code = (
        "def f(x):\n"
        "    try:\n"
        "        x()\n"
        "    except Exception:\n"          # flagged
        "        pass\n"
        "    try:\n"
        "        x()\n"
        "    except (OSError, RuntimeError):\n"  # ok: narrowed
        "        pass\n"
        "    try:\n"
        "        x()\n"
        "    except Exception as e:\n"     # ok: logged
        "        print(e)\n"
        "    try:\n"
        "        x()\n"
        "    except:\n"                    # flagged: bare
        "        pass\n")
    bad = _lint(tmp_path, code, "silent-except")
    assert [v.line for v in bad] == [4, 16]


def test_codec_symmetry_missing_decode(tmp_path):
    bad = _lint(tmp_path, (
        "class T:\n"
        "    def encode_payload(self, e):\n"
        "        e.u32(self.x)\n"), "codec-symmetry")
    assert len(bad) == 1 and bad[0].detail == "missing-decode"


def test_codec_symmetry_transposed_fields(tmp_path):
    code = (
        "class T:\n"
        "    def encode_payload(self, e):\n"
        "        e.u32(self.a)\n"
        "        e.u32(self.b)\n"
        "    def decode_payload(self, d):\n"
        "        self.b = d.u32()\n"       # transposed vs encode
        "        self.a = d.u32()\n")
    bad = _lint(tmp_path, code, "codec-symmetry")
    assert len(bad) == 1 and bad[0].detail.startswith("order:")
    ok = _lint(tmp_path, code.replace(
        "        self.b = d.u32()\n        self.a = d.u32()\n",
        "        self.a = d.u32()\n        self.b = d.u32()\n"),
        "codec-symmetry")
    assert not ok


def test_codec_symmetry_version_tolerance(tmp_path):
    intolerant = (
        "class T:\n"
        "    VERSION = 2\n"
        "    def encode_payload(self, e):\n"
        "        e.u32(self.a)\n"
        "        e.u32(self.b)\n"
        "    def decode_payload(self, d):\n"
        "        self.a = d.u32()\n"
        "        self.b = d.u32()\n")      # blind v2 read of a v1 blob
    bad = _lint(tmp_path, intolerant, "codec-symmetry")
    assert len(bad) == 1 and bad[0].detail == "no-old-version-tolerance"
    tolerant = intolerant.replace(
        "        self.b = d.u32()\n",
        "        if d.remaining_in_frame():\n"
        "            self.b = d.u32()\n"
        "        else:\n"
        "            self.b = 0\n")
    assert not _lint(tmp_path, tolerant, "codec-symmetry")


def test_codec_symmetry_struct_v_gated_ok(tmp_path):
    """PR 19: a decode_payload keying an optional tail on the sender's
    struct_v (Message.struct_v, set from d.start() by the decode
    harness) is version-tolerant — the sanctioned gate when a message
    carries both a versioned tail and the bare trace tail."""
    ok = _lint(tmp_path, (
        "class T:\n"
        "    VERSION = 2\n"
        "    def encode_payload(self, e):\n"
        "        e.u32(self.a)\n"
        "        e.u32(self.b)\n"
        "    def decode_payload(self, d):\n"
        "        self.a = d.u32()\n"
        "        if self.struct_v >= 2:\n"
        "            self.b = d.u32()\n"
        "        else:\n"
        "            self.b = 0\n"), "codec-symmetry")
    assert not ok


def test_codec_symmetry_start_gated_struct_ok(tmp_path):
    ok = _lint(tmp_path, (
        "class S:\n"
        "    def encode(self, e):\n"
        "        e.start(2, 1)\n"
        "        e.u32(self.a)\n"
        "        e.finish()\n"
        "    @classmethod\n"
        "    def decode(cls, d):\n"
        "        v = d.start(2)\n"
        "        out = cls(a=d.u32())\n"
        "        if v >= 2:\n"
        "            out.b = d.u32()\n"
        "        d.end()\n"
        "        return out\n"), "codec-symmetry")
    assert not ok


def test_blocking_flags_sleep_in_async_def(tmp_path):
    code = (
        "import asyncio, time\n"
        "async def pump():\n"
        "    time.sleep(0.1)\n"            # flagged: sync sleep on loop
        "    await asyncio.sleep(0.1)\n")  # ok: awaited
    bad = _lint(tmp_path, code, "no-blocking-on-loop")
    assert [v.line for v in bad] == [3]


def test_blocking_follows_fast_dispatch_call_graph(tmp_path):
    code = (
        "class D:\n"
        "    def ms_can_fast_dispatch(self, msg):\n"
        "        return True\n"
        "    def ms_dispatch(self, conn, msg):\n"
        "        self._helper()\n"
        "        return True\n"
        "    def _helper(self):\n"
        "        self.lock.acquire()\n"    # flagged via the call graph
        "        self.guard.acquire(blocking=False)\n")  # ok: non-block
    bad = _lint(tmp_path, code, "no-blocking-on-loop")
    assert [v.line for v in bad] == [8]


def test_blocking_ignores_plain_dispatcher(tmp_path):
    ok = _lint(tmp_path, (
        "class D:\n"
        "    def ms_can_fast_dispatch(self, msg):\n"
        "        return False\n"           # slow path only: pool thread
        "    def ms_dispatch(self, conn, msg):\n"
        "        self.lock.acquire()\n"
        "        return True\n"), "no-blocking-on-loop")
    assert not ok


def test_jax_purity_flags_np_and_time_in_traced_fn(tmp_path):
    code = (
        "import jax\n"
        "import numpy as np\n"
        "import time\n"
        "@jax.jit\n"
        "def kernel(x):\n"
        "    t = time.time()\n"            # flagged
        "    return np.sum(x) + t\n"       # flagged
        "def untraced(x):\n"
        "    return np.sum(x)\n")          # ok: not traced
    bad = _lint(tmp_path, code, "jax-purity")
    assert sorted(v.detail for v in bad) == ["np.sum", "time.time"]


def test_jax_purity_follows_pallas_call_kernel(tmp_path):
    code = (
        "from jax.experimental import pallas as pl\n"
        "import numpy as np\n"
        "def _kern(ref, o_ref):\n"
        "    o_ref[...] = np.dot(ref[...], ref[...])\n"  # flagged
        "def run(x):\n"
        "    return pl.pallas_call(_kern, out_shape=None)(x)\n")
    bad = _lint(tmp_path, code, "jax-purity")
    assert len(bad) == 1 and bad[0].detail == "np.dot"


def test_d2h_flags_materializers_in_fast_dispatch_graph(tmp_path):
    code = (
        "import numpy as np\n"
        "class D:\n"
        "    def ms_can_fast_dispatch(self, msg):\n"
        "        return True\n"
        "    def ms_dispatch(self, conn, msg):\n"
        "        self._helper(msg)\n"
        "        return True\n"
        "    def _helper(self, msg):\n"
        "        a = np.asarray(msg.buf)\n"      # flagged: d2h fetch
        "        b = bytes(msg.buf)\n"           # flagged
        "        c = msg.buf.tolist()\n"         # flagged
        "        n = len(msg.buf)\n")            # ok: metadata
    bad = _lint(tmp_path, code, "no-d2h-on-hot-path")
    assert [v.line for v in bad] == [9, 10, 11]


def test_d2h_follows_stripe_queue_worker(tmp_path):
    # the queue worker root is resolved by module path: write the
    # fixture AS ceph_tpu/tpu/queue.py so the root matches
    code = (
        "import numpy as np\n"
        "class StripeBatchQueue:\n"
        "    def _worker(self):\n"
        "        self._run_batch([])\n"
        "    def _run_batch(self, batch):\n"
        "        return np.asarray(batch)\n")    # flagged via worker
    bad = _lint(tmp_path, code, "no-d2h-on-hot-path",
                rel="ceph_tpu/tpu/queue.py")
    assert [v.line for v in bad] == [6]
    # a plain class's methods are NOT roots
    ok = _lint(tmp_path, (
        "import numpy as np\n"
        "class Other:\n"
        "    def _run_batch(self, batch):\n"
        "        return np.asarray(batch)\n"), "no-d2h-on-hot-path")
    assert not ok


def test_d2h_hard_paths_never_baseline(tmp_path):
    """Violations in the device-path modules are excluded from
    --write-baseline output: debt there can never be accepted."""
    from ceph_tpu.analysis.framework import (Violation,
                                             violations_to_baseline)

    hard = Violation(check="no-d2h-on-hot-path",
                     path="ceph_tpu/tpu/staging.py", line=1,
                     scope="DeviceBuf.x", detail="bytes()", message="m")
    soft = Violation(check="no-d2h-on-hot-path",
                     path="ceph_tpu/osd/backend.py", line=1,
                     scope="ECBackend.x", detail="bytes()", message="m")
    entries = violations_to_baseline([hard, soft])["entries"]
    assert soft.key in entries and hard.key not in entries


def test_parse_error_is_a_violation(tmp_path):
    p = tmp_path / "broken.py"
    p.write_text("def f(:\n")
    vs = run_checks([SourceFile(str(p), "ceph_tpu/broken.py")], ALL_CHECKS)
    assert len(vs) == 1 and vs[0].check == "parse-error"


def test_baseline_allows_exact_count_only(tmp_path):
    code = ("import threading\n"
            "a = threading.Lock()\n"
            "b = threading.Lock()\n")
    p = tmp_path / "m.py"
    p.write_text(code)
    vs = run_checks([SourceFile(str(p), "ceph_tpu/m.py")],
                    [CHECKS_BY_NAME["named-locks"]])
    assert len(vs) == 2
    key = vs[0].key
    assert not new_violations(vs, {key: 2})      # both baselined
    over = new_violations(vs, {key: 1})          # one new beyond debt
    assert len(over) == 1 and over[0].line == 3  # newest-looking first


def test_failpoint_names_flag_typo_and_dynamic(tmp_path):
    bad = _lint(tmp_path, (
        "from ceph_tpu.core import failpoint as fp\n"
        "def f():\n"
        "    fp.failpoint('pg.commit.client_repyl')\n"  # typo'd
    ), "failpoint-name-registry")
    assert len(bad) == 1 and "typo" in bad[0].message

    dyn = _lint(tmp_path, (
        "from ceph_tpu.core import failpoint as fp\n"
        "def f(name):\n"
        "    fp.failpoint(name)\n"
    ), "failpoint-name-registry")
    assert len(dyn) == 1 and "dynamic" in dyn[0].detail

    ok = _lint(tmp_path, (
        "from ceph_tpu.core import failpoint as fp\n"
        "def f():\n"
        "    fp.failpoint('pg.commit.client_reply')\n"
        "    if fp.enabled('msg.frame.deliver'):\n"
        "        fp.failpoint('msg.frame.deliver')\n"
    ), "failpoint-name-registry")
    assert not ok

    # bare Event.wait()-style calls must not false-positive
    clean = _lint(tmp_path, (
        "def f(ev):\n"
        "    ev.enabled('whatever')\n"
        "    arm = None\n"
    ), "failpoint-name-registry")
    assert not clean


def test_span_discipline_unfinished_span(tmp_path):
    bad = _lint(tmp_path, (
        "def f(tr):\n"
        "    s = tr.start_span('x')\n"
        "    s.annotate('commit')\n"  # never finished
    ), "span-discipline")
    assert any("finish" in v.message for v in bad)

    # a bare call nothing can ever finish
    bare = _lint(tmp_path, (
        "def f(tr):\n"
        "    tr.start_span('x')\n"
    ), "span-discipline")
    assert any(v.detail == "start_span-unfinished" for v in bare)

    ok = _lint(tmp_path, (
        "def f(tr):\n"
        "    with tr.start_span('x') as s:\n"
        "        s.annotate('commit')\n"
        "def g(tr):\n"
        "    s = tr.start_span('y')\n"
        "    def cb():\n"
        "        s.finish()\n"  # closure finish counts
        "    return cb\n"
        "def h(tr, op):\n"
        "    op.span = tr.start_span('z')\n"
        "def h2(op):\n"
        "    op.span.finish()\n"  # sibling-method finish (module-wide)
    ), "span-discipline")
    assert not [v for v in ok if v.detail == "start_span-unfinished"]


def test_span_discipline_stage_registry(tmp_path):
    bad = _lint(tmp_path, (
        "def f(top):\n"
        "    top.mark_event('comit_sent')\n"  # typo'd stage
    ), "span-discipline")
    assert len(bad) == 1 and "not declared" in bad[0].message

    dyn = _lint(tmp_path, (
        "def f(top, name):\n"
        "    top.mark_event(name)\n"
    ), "span-discipline")
    assert len(dyn) == 1 and "<dynamic>" in dyn[0].detail

    # literal annotate must be a stage; f-string detail is free-form
    lit = _lint(tmp_path, (
        "def f(span, r):\n"
        "    span.annotate('not_a_stage')\n"
        "    span.annotate(f'reply result={r}')\n"
    ), "span-discipline")
    assert len(lit) == 1 and "not_a_stage" in lit[0].detail

    ok = _lint(tmp_path, (
        "def f(top, self, msg):\n"
        "    top.mark_event('commit_sent')\n"
        "    self._op_stage(msg, 'admitted')\n"
    ), "span-discipline")
    assert not ok


def test_span_discipline_recorder_span_registry(tmp_path):
    """tracing.span names are held to tracing.SPANS as stage names are
    to STAGES."""
    bad = _lint(tmp_path, (
        "from ceph_tpu.core import tracing\n"
        "def f():\n"
        "    with tracing.span('batch.stak'):\n"  # typo'd span
        "        pass\n"
    ), "span-discipline")
    assert len(bad) == 1 and "tracing.SPANS" in bad[0].message

    dyn = _lint(tmp_path, (
        "from ceph_tpu.core import tracing\n"
        "def f(name):\n"
        "    with tracing.span(name):\n"
        "        pass\n"
    ), "span-discipline")
    assert len(dyn) == 1 and "<dynamic>" in dyn[0].detail

    ok = _lint(tmp_path, (
        "from ceph_tpu.core import tracing\n"
        "def f(n):\n"
        "    with tracing.span('batch.stack', cols=n):\n"
        "        pass\n"
    ), "span-discipline")
    assert not ok


def test_span_discipline_never_baseline(tmp_path):
    from ceph_tpu.analysis.framework import (Violation,
                                             violations_to_baseline)

    v = Violation(check="span-discipline",
                  path="ceph_tpu/osd/pg.py", line=1,
                  scope="PG.x", detail="start_span-unfinished",
                  message="m")
    assert v.key not in violations_to_baseline([v])["entries"]


def test_no_unwatched_jit_flags_every_raw_spelling(tmp_path):
    code = (
        "import functools\n"
        "import jax\n"
        "from jax.experimental import pallas as pl\n"
        "f = jax.jit(lambda x: x)\n"                    # call
        "@jax.jit\n"                                    # decorator
        "def g(x):\n"
        "    return x\n"
        "h = functools.partial(jax.jit, static_argnames=('n',))\n"
        "def k(kern):\n"
        "    return pl.pallas_call(kern, out_shape=None)\n")
    bad = _lint(tmp_path, code, "no-unwatched-jit")
    assert [v.line for v in bad] == [4, 5, 8, 10]
    # importing the raw entry point by name is flagged too
    imp = _lint(tmp_path, (
        "from jax import jit\n"
        "from jax.experimental.pallas import pallas_call\n"),
        "no-unwatched-jit")
    assert [v.line for v in imp] == [1, 2]
    # the devwatch wrappers are the sanctioned spelling
    ok = _lint(tmp_path, (
        "from ceph_tpu.tpu.devwatch import instrumented_jit\n"
        "f = instrumented_jit(lambda x: x, family='fam')\n"),
        "no-unwatched-jit")
    assert not ok
    # devwatch itself is exempt (it owns the raw entry points)
    exempt = _lint(tmp_path, (
        "import jax\n"
        "f = jax.jit(lambda x: x)\n"), "no-unwatched-jit",
        rel="ceph_tpu/tpu/devwatch.py")
    assert not exempt


def test_no_unwatched_jit_never_baseline(tmp_path):
    from ceph_tpu.analysis.framework import (Violation,
                                             violations_to_baseline)

    v = Violation(check="no-unwatched-jit",
                  path="ceph_tpu/ops/newkernel.py", line=1,
                  scope="f", detail="jax.jit", message="m")
    assert v.key not in violations_to_baseline([v])["entries"]


def test_jax_purity_follows_instrumented_jit(tmp_path):
    """The devwatch wrappers are trace entry points for purity
    analysis too — converting jax.jit -> instrumented_jit must not
    blind the jax-purity check."""
    code = (
        "import numpy as np\n"
        "from ceph_tpu.tpu.devwatch import instrumented_jit\n"
        "def kernel(x):\n"
        "    return np.sum(x)\n"               # flagged: np in traced fn
        "f = instrumented_jit(kernel, family='fam')\n")
    bad = _lint(tmp_path, code, "jax-purity")
    assert len(bad) == 1 and bad[0].detail == "np.sum"


def test_qos_class_registry_flags_typo(tmp_path):
    bad = _lint(tmp_path, (
        "def f(wq, pgid, run):\n"
        "    wq.queue(pgid, run, qos_class='recvery')\n"  # typo'd
    ), "qos-class-registry")
    assert len(bad) == 1 and "best_effort" in bad[0].message

    ok = _lint(tmp_path, (
        "def f(wq, pgid, run, qcls):\n"
        "    wq.queue(pgid, run, qos_class='recovery')\n"
        "    wq.queue(pgid, run, qos_class='snaptrim')\n"
        "    wq.queue(pgid, run, qos_class=qcls)\n"  # classify_op path
    ), "qos-class-registry")
    assert not ok


def test_qos_class_registry_never_baseline(tmp_path):
    from ceph_tpu.analysis.framework import (Violation,
                                             violations_to_baseline)

    v = Violation(check="qos-class-registry",
                  path="ceph_tpu/osd/daemon.py", line=1,
                  scope="OSDService.x", detail="qos_class='typo'",
                  message="m")
    assert v.key not in violations_to_baseline([v])["entries"]


def test_failpoint_names_never_baseline(tmp_path):
    from ceph_tpu.analysis.framework import (Violation,
                                             violations_to_baseline)

    v = Violation(check="failpoint-name-registry",
                  path="ceph_tpu/osd/pg.py", line=1,
                  scope="PG.x", detail="failpoint('typo')", message="m")
    assert v.key not in violations_to_baseline([v])["entries"]


def test_no_unverified_read_flags_every_bypass_shape(tmp_path):
    code = (
        "from ceph_tpu.store.objectstore import ObjectStore\n"
        "class MyStore(ObjectStore):\n"
        "    def read(self, cid, oid, off=0, length=0):\n"  # flagged:
        "        pass\n"                       # shadows the verify gate
        "    def _read_span(self, cid, oid, off, length):\n"  # ok: the
        "        pass\n"                       # sanctioned backend hook
        "def peek(store, cid, oid):\n"
        "    return store._read_span(cid, oid, 0, 0)\n"  # flagged: raw
        "def disable(store):\n"
        "    store.verify_reads = False\n"     # flagged: hard-disable
        "def conf_gate(store, ctx):\n"
        "    store.verify_reads = bool(ctx)\n"  # ok: runtime-computed
        "class Bystander:\n"
        "    def read(self):\n"                # ok: not an ObjectStore
        "        pass\n")
    bad = _lint(tmp_path, code, "no-unverified-read")
    assert [v.line for v in bad] == [3, 8, 10]


def test_no_unverified_read_allows_the_gate_itself(tmp_path):
    ok = _lint(tmp_path, (
        "class ObjectStore:\n"
        "    def read(self, cid, oid, off=0, length=0):\n"
        "        data, size, seals = self._read_span(cid, oid, 0, 0)\n"
        "        return data\n"),
        "no-unverified-read", rel="ceph_tpu/store/objectstore.py")
    assert not ok


def test_no_unverified_read_never_baseline(tmp_path):
    from ceph_tpu.analysis.framework import (Violation,
                                             violations_to_baseline)

    v = Violation(check="no-unverified-read",
                  path="ceph_tpu/osd/backend.py", line=1,
                  scope="ECBackend.x", detail="_read_span(...)",
                  message="m")
    assert v.key not in violations_to_baseline([v])["entries"]


# -- shape-bucket-discipline (PR 17) ------------------------------------


def test_shape_bucket_flags_undeclared_family(tmp_path):
    bad = _lint(tmp_path, (
        "from ceph_tpu.tpu.devwatch import instrumented_jit\n"
        "import functools\n"
        "f = instrumented_jit(lambda x: x, family='mystery_kernel')\n"
        "@functools.partial(instrumented_jit, family='other_rogue')\n"
        "def g(x):\n"
        "    return x\n"), "shape-bucket-discipline")
    assert sorted(v.detail for v in bad) == [
        "undeclared-family:mystery_kernel",
        "undeclared-family:other_rogue"]


def test_shape_bucket_allows_declared_families(tmp_path):
    ok = _lint(tmp_path, (
        "from ceph_tpu.tpu.devwatch import instrumented_jit\n"
        "f = instrumented_jit(lambda x: x, family='gf256_swar')\n"
        "g = instrumented_jit(lambda x: x, family='crush_mapper')\n"),
        "shape-bucket-discipline")
    assert not ok


def test_shape_bucket_flags_unpadded_queue_dispatch(tmp_path):
    code = (
        "def dispatch(codec, stacked):\n"
        "    return codec.encode_array(stacked)\n"
        "def padded(codec, stacked, covering):\n"
        "    w = covering(stacked.shape[1])\n"
        "    return codec.encode_array(stacked)\n")
    bad = _lint(tmp_path, code, "shape-bucket-discipline",
                rel="ceph_tpu/tpu/queue.py")
    assert [v.detail for v in bad] == ["unpadded-dispatch:encode_array"]
    # the same code outside the coalescer is not this check's business
    assert not _lint(tmp_path, code, "shape-bucket-discipline",
                     rel="ceph_tpu/osd/other.py")


def test_shape_bucket_flags_unpadded_clay_dispatch(tmp_path):
    """PR 19: the clay array-codec kernels (repair_planes /
    decode_planes) are dispatch tails too — an unpadded coupled-layer
    batch is the same fresh-compile-per-width hazard as the flat
    matmul."""
    code = (
        "def dispatch_array(codec, stacked):\n"
        "    out = codec.repair_planes(0, [1, 2], stacked)\n"
        "    return codec.decode_planes([1, 2, 3], stacked)\n"
        "def padded(codec, stacked, covering):\n"
        "    w = covering(stacked.shape[2], 1)\n"
        "    return codec.repair_planes(0, [1, 2], stacked)\n")
    bad = _lint(tmp_path, code, "shape-bucket-discipline",
                rel="ceph_tpu/tpu/queue.py")
    assert sorted(v.detail for v in bad) == [
        "unpadded-dispatch:decode_planes",
        "unpadded-dispatch:repair_planes"]


def test_shape_bucket_gf256_clay_family_declared():
    """The clay kernel family registered by gf256_swar must be in the
    declared bucket set — otherwise every crep/cdec compile counts as
    a rogue and the steady guard can never arm on a clay pool."""
    from ceph_tpu.tpu import shapebucket

    assert "gf256_clay" in set(shapebucket.declared_families())


def test_shape_bucket_never_baseline(tmp_path):
    from ceph_tpu.analysis.framework import (Violation,
                                             violations_to_baseline)

    v = Violation(check="shape-bucket-discipline",
                  path="ceph_tpu/tpu/queue.py", line=1,
                  scope="dispatch", detail="unpadded-dispatch:encode_array",
                  message="m")
    assert v.key not in violations_to_baseline([v])["entries"]


def test_shape_bucket_clean_on_repo_tree():
    """The real tree must carry zero violations: every registration
    site's family is declared and every coalescer dispatch pads."""
    from ceph_tpu.analysis.framework import discover_files, run_checks
    from ceph_tpu.analysis.checks import CHECKS_BY_NAME as _BY_NAME

    files = [f for f in discover_files(subdirs=("ceph_tpu",))]
    vs = run_checks(files, [_BY_NAME["shape-bucket-discipline"]])
    assert not vs, [v.message for v in vs]


# -- lane-capability (PR 18) --------------------------------------------


def test_lane_capability_flags_pg_lock_from_fast_dispatch(tmp_path):
    code = (
        "class Svc:\n"
        "    def ms_can_fast_dispatch(self, m):\n"
        "        return True\n"
        "    def ms_dispatch(self, m, pg):\n"
        "        self._apply(pg)\n"
        "    def _apply(self, pg):\n"
        "        with pg.lock:\n"
        "            pass\n")
    bad = _lint(tmp_path, code, "lane-capability")
    assert len(bad) == 1
    v = bad[0]
    assert v.line == 7 and v.detail.startswith("loop:may-take-pg-lock")
    # the message names the propagation chain, not just the site
    assert "ms_dispatch" in v.message
    # a try-acquire cannot deadlock the lane: exempt
    ok = _lint(tmp_path, code.replace(
        "with pg.lock:\n            pass",
        "pg.lock.acquire(blocking=False)"), "lane-capability")
    assert not ok


def test_lane_capability_flags_compile_on_loop(tmp_path):
    bad = _lint(tmp_path, (
        "import jax\n"
        "async def handle(fn):\n"
        "    return jax.jit(fn)\n"), "lane-capability")
    assert [v.detail for v in bad] == ["loop:may-compile:jax.jit()"]
    # the same compile from a plain thread target is fine
    ok = _lint(tmp_path, (
        "import jax\n"
        "import threading\n"
        "def warm(fn):\n"
        "    return jax.jit(fn)\n"
        "def boot(fn):\n"
        "    threading.Thread(target=warm).start()\n"), "lane-capability")
    assert not ok


def test_lane_capability_never_baseline():
    from ceph_tpu.analysis.framework import (Violation,
                                             violations_to_baseline)

    v = Violation(check="lane-capability", path="ceph_tpu/osd/osd.py",
                  line=1, scope="Svc._apply",
                  detail="loop:may-take-pg-lock:with pg.lock",
                  message="m")
    assert v.key not in violations_to_baseline([v])["entries"]


# -- lock-order-cycle (PR 18) -------------------------------------------


_CYCLE_MODULE = (
    "from ceph_tpu.core.lockdep import make_lock\n"
    "class S:\n"
    "    def __init__(self):\n"
    "        self.a = make_lock('A')\n"
    "        self.b = make_lock('B')\n"
    "        self.c = make_lock('C')\n"
    "    def ab(self):\n"
    "        with self.a:\n"
    "            with self.b:\n"
    "                pass\n"
    "    def bc(self):\n"
    "        with self.b:\n"
    "            with self.c:\n"
    "                pass\n"
    "    def ca(self):\n"
    "        with self.c:\n"
    "            with self.a:\n"
    "                pass\n")


def test_lock_cycle_flags_three_lock_cycle(tmp_path):
    bad = _lint(tmp_path, _CYCLE_MODULE, "lock-order-cycle")
    assert len(bad) == 1
    assert bad[0].detail.startswith("cycle:")
    for name in ("A", "B", "C"):
        assert name in bad[0].detail
    # breaking one edge (ca takes them in the global order) is clean
    ok = _lint(tmp_path, _CYCLE_MODULE.replace(
        "        with self.c:\n            with self.a:",
        "        with self.a:\n            with self.c:"),
        "lock-order-cycle")
    assert not ok


def test_lock_cycle_never_baseline():
    from ceph_tpu.analysis.framework import (Violation,
                                             violations_to_baseline)

    v = Violation(check="lock-order-cycle", path="ceph_tpu/osd/pg.py",
                  line=0, scope="<lock-graph>", detail="cycle:A->B->A",
                  message="m")
    assert v.key not in violations_to_baseline([v])["entries"]


def test_lock_graph_dump_round_trip(tmp_path):
    import json

    from ceph_tpu.analysis.checks.lock_cycle import LockModel

    p = tmp_path / "mod.py"
    p.write_text(_CYCLE_MODULE)
    model = LockModel.of([SourceFile(str(p), "ceph_tpu/mod.py")])
    data = json.loads(json.dumps(model.to_json()))
    assert data["edges"]["A"].keys() == {"B"}
    assert data["cycles"] and sorted(data["cycles"][0][:-1]) == \
        ["A", "B", "C"]
    dot = model.to_dot()
    assert '"A" -> "B"' in dot
    # cycle edges are highlighted for the graphviz eye
    assert "[color=red]" in dot


# -- unguarded-shared-state (PR 18) -------------------------------------


def test_shared_state_flags_cross_role_unguarded_read(tmp_path):
    code = (
        "import threading\n"
        "from ceph_tpu.core.lockdep import make_lock\n"
        "class Stats:\n"
        "    def __init__(self):\n"
        "        self._lock = make_lock('stats')\n"
        "        self._count = 0\n"
        "        threading.Thread(target=self._tick_loop).start()\n"
        "    def _tick_loop(self):\n"
        "        with self._lock:\n"
        "            self._count += 1\n"
        "    async def handle(self):\n"
        "        return self._count\n")
    bad = _lint(tmp_path, code, "unguarded-shared-state")
    assert [(v.scope, v.detail) for v in bad] == [("Stats", "_count")]
    assert "handle" in bad[0].message and "_tick_loop" in bad[0].message
    # the guarded read variant is clean
    ok = _lint(tmp_path, code.replace(
        "        return self._count",
        "        with self._lock:\n"
        "            return self._count"), "unguarded-shared-state")
    assert not ok


def test_shared_state_same_lane_is_sequential(tmp_path):
    # writer and reader on the SAME lane: no race, no violation
    ok = _lint(tmp_path, (
        "from ceph_tpu.core.lockdep import make_lock\n"
        "class Seq:\n"
        "    def __init__(self):\n"
        "        self._lock = make_lock('seq')\n"
        "        self._n = 0\n"
        "    def bump(self):\n"
        "        with self._lock:\n"
        "            self._n += 1\n"
        "    def peek(self):\n"
        "        return self._n\n"), "unguarded-shared-state")
    assert not ok


# -- CLI: --changed / --write-baseline / --lock-graph (PR 18) -----------


def test_cli_changed_scopes_reporting():
    import contextlib
    import io
    import json

    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = cephlint.main(["--json", "--changed",
                            "--checks", "no-sleep-poll"])
    out = json.loads(buf.getvalue())
    assert rc == 0
    assert out["changed_vs"] == "HEAD"
    assert out["new"] == []


def test_cli_write_baseline_prunes_stale_keys(tmp_path):
    import contextlib
    import io
    import json

    stale = "no-sleep-poll::ceph_tpu/gone.py::nobody::deleted"
    bl = tmp_path / "baseline.json"
    bl.write_text(json.dumps(
        {"comment": "test", "entries": {stale: 3}}))
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = cephlint.main(["--write-baseline", "--baseline", str(bl),
                            "--checks", "no-sleep-poll"])
    out = buf.getvalue()
    assert rc == 0
    assert f"- {stale}" in out, out
    rewritten = json.loads(bl.read_text())["entries"]
    assert stale not in rewritten


def test_cli_lock_graph_json():
    import contextlib
    import io
    import json

    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = cephlint.main(["--lock-graph", "json"])
    out = json.loads(buf.getvalue())
    assert rc == 0
    assert out["cycles"] == [], out["cycles"]
    # the real tree's graph is non-trivial: the PG lock orders ahead
    # of per-subsystem locks
    assert out["edges"], "static graph is empty"
