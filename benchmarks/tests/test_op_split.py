"""The served write split end to end (PR 39): the nine per-layer metrics
that read the client's timeline joined to the primary's, and the
worker's time off the CPU.  Each has its manifest entry, its metric file
and its reader, none is read in a CRUSH cell, and the write cell's
rehearsal prints all nine.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_op_split.py -q

Written to the names it adds, so that a later append does not fail it.
"""

from __future__ import annotations

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (BENCH, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

import run  # noqa: E402

WRITE = "ec-k8m4-write-1MiB"
OPS = ["op_send_ms", "op_exec_ms", "op_fanout_wait_ms", "op_fanout_ms",
       "op_reply_back_ms", "op_tail_encode_ms", "op_tail_commit_wait_ms",
       "op_tail_other_ms"]
OFFCPU = "worker_offcpu_ms.write"
NEW = OPS + [OFFCPU]


def manifest() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_each_new_metric_has_its_entry_file_and_reader():
    entries = {m["name"]: m for m in manifest()["per_layer"]}
    for name in NEW:
        m = entries[name]
        assert m["source"] == "program_counter" and m["unit"] == "ms"
        assert m["better"] == "lower" and "workloads" not in m
        assert m["layer"] == ("stripe batch queue" if name == OFFCPU else
                              "client, messenger, PG pipeline, store")
        assert m["moves"] == ("write_MBps" if name == OFFCPU
                              else "op_p95_ms")
        how = run.metric_how(name)
        assert os.path.exists(os.path.join(BENCH, "readers",
                                           how["kind"] + ".py"))
    assert run.metric_how("op_tail_other_ms")["args"]["minus"] == [
        ["encode_queued", "encoded"], ["submitted", "commit"]]
    spans = run.metric_how(OFFCPU)["args"]["spans"]
    assert not {"dev.wait", "queue.idle", "queue.coalesce"} & set(spans)
    from ceph_tpu.core import tracing

    assert set(spans) <= set(tracing.SPANS)
    for name in OPS:
        args = run.metric_how(name)["args"]
        for stage in [args["from"], args["to"]] + [
                s for p in args.get("minus", []) for s in p]:
            assert stage in tracing.STAGES, (name, stage)


def test_no_crush_cell_reads_them():
    for w in manifest()["workloads"]:
        names = {m["name"] for m in run.load_cell(w["name"])["per_layer"]}
        if w["name"].startswith("crush"):
            assert not set(NEW) & names, w["name"]
        else:
            assert set(NEW) <= names, w["name"]


def test_the_write_cells_rehearsal_prints_all_nine():
    r = run.run_cell(WRITE, 2_500_003_901, 2.0, True, require_chip=False,
                     traffic_over={"warm_batch_widths": [2],
                                   "check_shards_of": 6})
    assert r["correct"] is True and r["device"]["platform"] == "cpu"
    got = {k: v["value"] for k, v in r["metrics"].items()}
    for name in NEW:
        assert name in got, (name, sorted(got))
        assert r["metrics"][name]["unit"] == "ms"
    for name in ["op_send_ms", "op_exec_ms", "op_fanout_ms",
                 "op_reply_back_ms", "op_tail_encode_ms"]:
        assert got[name] > 0.0, (name, got[name])
    assert got[OFFCPU] >= 0.0
    # the tail's three stretches make the slowest twentieth's mean op,
    # which is no shorter than the queue's wait for it
    tail = (got["op_tail_encode_ms"] + got["op_tail_commit_wait_ms"]
            + got["op_tail_other_ms"])
    assert tail >= got["op_tail_encode_ms"] > 0.0
