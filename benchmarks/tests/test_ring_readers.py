"""The per-layer metrics that read the program's span ring (PR 27):
each reader on a ring recorded by hand, what a reader gives a program
without a recorder, and the write cell's rehearsal printing them.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_ring_readers.py -q
"""

from __future__ import annotations

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (BENCH, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

import run  # noqa: E402
from readers import op_stage_mean, span_self_per_batch  # noqa: E402

from ceph_tpu.core import tracing  # noqa: E402

WRITE = "ec-k8m4-write-1MiB"
BATCH = ["batch_stack_ms.write", "batch_crc_layout_ms.write",
         "batch_self_ms.write", "dev_dispatch_ms.write", "dev_wait_ms.write"]
IDLE = "worker_idle_ms.write"
OPS = ["op_pre_encode_ms", "op_commit_wait_ms", "op_reply_ms"]
MS = 1_000_000


def how(name: str) -> dict:
    return run.metric_how(name)


def read(name: str, ctx: dict):
    h = how(name)
    return {"span_self_per_batch": span_self_per_batch,
            "op_stage_mean": op_stage_mean}[h["kind"]].read(h["args"], ctx)


def recorded_ring(monkeypatch, capacity: int = 256) -> tracing.Recorder:
    """Two batches (seq 6 and 7) of queue 1 on thread 7, to the
    nanosecond, after one (seq 5) outside the window; an op that
    concluded inside the window, one outside it, and a read."""
    rec = tracing.Recorder(capacity)
    ids = iter(range(100, 1000))

    def span(name, t0, t1, parent=0, thread=7, **counts):
        i = next(ids)
        rec._file(i, name, t0 * MS, t1 * MS, thread, parent, (), counts)
        return i

    def batch(seq, t, waits=True):
        """idle 1 ms, coalesce 2 ms, then a 90 ms batch at t."""
        span("queue.idle", t - 3, t - 2, q=1, seq=seq)
        span("queue.coalesce", t - 2, t, q=1, seq=seq)
        b = next(ids)
        span("batch.stack", t + 1, t + 4, parent=b)               # 3
        e = next(ids)
        span("dev.dispatch", t + 5, t + 7, parent=e, family="gf256_pallas")
        span("dev.wait", t + 7, t + 10, parent=e)
        rec._file(e, "batch.encode", (t + 4) * MS, (t + 11) * MS, 7, b,
                  (), {})                                          # self 2
        span("batch.crc_layout", t + 11, t + 21, parent=b)        # 10
        c = next(ids)
        span("dev.dispatch", t + 22, t + 23, parent=c, family="crc32c_device")
        if waits:
            span("dev.wait", t + 23, t + 81, parent=c)            # 58
        rec._file(c, "batch.crc", (t + 21) * MS, (t + 82) * MS, 7, b,
                  (), {})                                          # self 2
        span("batch.fanout", t + 82, t + 84, parent=b)            # 2
        # a client thread's span over the same time is nobody's child
        span("dev.wait", t + 10, t + 60, thread=8)
        rec._file(b, "queue.batch", t * MS, (t + 90) * MS, 7, 0, (9,),
                  {"q": 1, "seq": seq, "kind": "encp", "jobs": 1})
        # queue.batch self: 90 - 3 - 7 - 10 - 61 - 2 = 7

    def op(t0_ms, stages, terminal="commit_sent"):
        events = [(0.0, "initiated", "")] + [
            (ms / 1e3, s, "") for s, ms in stages] + [
            (stages[-1][1] / 1e3 + 0.002, terminal, "")]
        rec._file(next(ids), tracing.OP_RECORD, t0_ms * MS,
                  t0_ms * MS + int(events[-1][0] * 1e9), 3, 0, (),
                  {"desc": "osd_op(w)", "reqid": "", "events": tuple(events)})

    write = [("queued_for_pg", 1), ("reached_pg", 2), ("admitted", 4),
             ("submitted", 100), ("commit", 140)]
    batch(5, 1000)
    op(900, write)                          # concluded before the window
    batch(6, 1100)
    op(1050, write)                         # inside: 4, 40, 2 ms
    op(1120, [("reached_pg", 1)], terminal="read_sent")   # a read
    batch(7, 1200)
    op(1190, [("queued_for_pg", 2), ("admitted", 8), ("submitted", 50),
              ("commit", 70)])              # inside: 8, 20, 2 ms
    monkeypatch.setattr(tracing, "_recorder", rec)
    return rec


def ctx_of(lo, hi) -> dict:
    return {"before": {"queue.batches": lo}, "after": {"queue.batches": hi}}


def test_each_reader_on_a_recorded_ring(monkeypatch):
    recorded_ring(monkeypatch)
    ctx = ctx_of(5, 7)
    got = {n: read(n, ctx) for n in BATCH + [IDLE] + OPS}
    assert got == pytest.approx({
        "batch_stack_ms.write": 3.0,
        "batch_crc_layout_ms.write": 10.0,
        "batch_self_ms.write": 7.0 + 2.0 + 2.0 + 2.0,
        "dev_dispatch_ms.write": 3.0,
        "dev_wait_ms.write": 61.0,        # the worker's, not the client's
        "worker_idle_ms.write": 3.0,
        "op_pre_encode_ms": 6.0,          # (4 + 8) / 2
        "op_commit_wait_ms": 30.0,        # (40 + 20) / 2
        "op_reply_ms": 2.0,
    }, rel=1e-9)
    # the five of the batch are its span, with idle the worker's cycle
    assert sum(got[n] for n in BATCH) == 90.0
    assert sum(got[n] for n in BATCH) + got[IDLE] == 93.0
    # one window a run, whichever reader asks first
    assert ctx["ring_window"].batches == 2 and len(ctx["ring_window"].ops) == 3


@pytest.mark.parametrize("lo,hi,why", [
    (7, 7, "no batch ran"), (7, 9, "batches never recorded"),
    (3, 7, "a batch of the range is not in the ring")])
def test_an_empty_or_partial_range_reads_nothing(monkeypatch, lo, hi, why):
    recorded_ring(monkeypatch)
    assert [read(n, ctx_of(lo, hi)) for n in BATCH + [IDLE] + OPS] \
        == [None] * 9, why


def test_a_wrapped_ring_reads_nothing(monkeypatch):
    """43 records in a ring of 20: batch 5 is overwritten and batch 6's
    first nine records with it, so a window with batch 6 in it reads
    nothing, never a number from the records that are left."""
    rec = recorded_ring(monkeypatch, capacity=20)
    assert rec.overwritten == 23
    assert [read(n, ctx_of(5, 7)) for n in BATCH + [IDLE] + OPS] == [None] * 9
    assert read("dev_wait_ms.write", ctx_of(6, 7)) == 61.0


def test_a_name_with_no_span_in_the_window_is_left_out(monkeypatch):
    rec = tracing.Recorder(64)
    with rec.span("queue.batch", q=1, seq=1, jobs=1):
        with rec.span("batch.stack"):
            pass
    monkeypatch.setattr(tracing, "_recorder", rec)
    ctx = ctx_of(0, 1)
    assert read("batch_stack_ms.write", ctx) is not None
    assert read("dev_wait_ms.write", ctx) is None      # never 0
    assert read("op_reply_ms", ctx) is None


def test_a_program_without_the_recorder_reads_nothing(monkeypatch):
    """The driver lays these files over the parent's checkout too: its
    `core/tracing.py` has no recorder, and its driver of another kind
    keeps no `queue.batches`.  Nothing, and no exception."""
    monkeypatch.delattr(tracing, "batch_window")
    assert [read(n, ctx_of(0, 3)) for n in BATCH + [IDLE] + OPS] == [None] * 9
    monkeypatch.undo()
    assert read("batch_self_ms.write", {"before": {}, "after": {}}) is None


def test_the_manifest_lists_the_nine_after_the_nine():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        per_layer = json.load(f)["per_layer"]
    names = [m["name"] for m in per_layer]
    assert names[9:] == BATCH[:3] + [IDLE] + BATCH[3:] + OPS
    for m in per_layer[9:]:
        assert m["source"] == "program_counter" and m["unit"] == "ms"
        assert "workloads" not in m
        stem = m["name"].split(".")[0]
        assert tracing.OP_RECORD == "op" if stem.startswith("op_") else \
            stem in tracing.SPANS.values()
        for name in how(m["name"])["args"].get("spans", []):
            assert tracing.SPANS[name] == stem
    layers = {m["name"]: m["layer"] for m in per_layer[9:]}
    assert layers["dev_wait_ms.write"] == "kernels"
    assert layers["batch_self_ms.write"] == "stripe batch queue"
    assert layers["op_reply_ms"] == "client, messenger, PG pipeline, store"


def test_the_write_cells_rehearsal_prints_them(monkeypatch):
    """`--trace 1` on the CPU: the queue's four and the op's three, and
    the two `dev.*` ones (the crc call goes through instrumented_jit on
    every backend).  The five of the batch add up to what
    `queue_device_ms.write` times and a little more: that histogram
    stops before the fan-out and the accounting."""
    r = run.run_cell(WRITE, 2_500_000_027, 2.0, True, require_chip=False,
                     traffic_over={"warm_batch_widths": [2],
                                   "check_shards_of": 6})
    assert r["correct"] is True and r["device"]["platform"] == "cpu"
    got = {k: v["value"] for k, v in r["metrics"].items()}
    for name in BATCH + [IDLE] + OPS:
        assert name in got and got[name] >= 0.0, (name, got)
        assert r["metrics"][name]["unit"] == "ms"
    # the nine that were there still are (the trace's three have no
    # device plane to read on the CPU)
    assert {"queue_jobs_per_batch.write", "queue_device_ms.write",
            "queue_wait_ms", "inline_compiles.write"} <= set(got)
    whole = sum(got[n] for n in BATCH)
    assert got["queue_device_ms.write"] <= whole * 1.001
    assert whole <= got["queue_device_ms.write"] * 1.5 + 1.0
    assert got["dev_wait_ms.write"] > 0 and got["batch_stack_ms.write"] > 0
    assert got["op_commit_wait_ms"] > 0


def test_trace_0_output_keeps_its_shape():
    r = run.run_cell(WRITE, 2_500_000_029, 1.0, False, require_chip=False,
                     traffic_over={"warm_batch_widths": [2],
                                   "check_shards_of": 6})
    assert set(r["metrics"]) == {"write_MBps", "op_p95_ms", "setup_s"}
    assert "breakdown" not in r
