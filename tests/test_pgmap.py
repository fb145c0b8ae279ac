"""Cluster telemetry units: PGStat codec, SnapshotRing rates, the
PGMap digest, the new health checks, the Prometheus exposition format,
and the mgr ProgressModule's converging ETAs.

Reference roles: src/mon/PGMap.{h,cc} (stat aggregation + digest),
src/mon/HealthMonitor.cc (checks + mutes), the mgr progress and
prometheus modules.
"""

import re
import time

import numpy as np
import pytest

from ceph_tpu.core.config import Config
from ceph_tpu.core.context import Context
from ceph_tpu.core.encoding import Decoder, Encoder
from ceph_tpu.core.perf import SnapshotRing
from ceph_tpu.mon import messages as mm
from ceph_tpu.mon.pgmap import PGMapService
from ceph_tpu.osd.types import EVersion, PGStat


class Clock:
    def __init__(self, t: float = 1000.0) -> None:
        self.t = t

    def __call__(self) -> float:
        return self.t


def mkstat(pool=1, ps=0, state="active", primary=True, n=10,
           nbytes=4096, degraded=0, misplaced=0, unfound=0,
           log_size=5, **io) -> PGStat:
    return PGStat(pgid=(pool, ps), state=state, primary=primary,
                  num_objects=n, num_bytes=nbytes, log_size=log_size,
                  degraded=degraded, misplaced=misplaced,
                  unfound=unfound, last_update=EVersion(3, 7), **io)


# -- PGStat codec -------------------------------------------------------------

def test_pgstat_roundtrip_and_legacy_row():
    s = mkstat(pool=2, ps=5, state="active+degraded", degraded=12,
               misplaced=3, unfound=1, cl_wr_ops=9, cl_wr_bytes=9216,
               cl_rd_ops=4, cl_rd_bytes=2048, rec_ops=7, rec_bytes=7168)
    e = Encoder()
    s.encode(e)
    back = PGStat.decode(Decoder(e.bytes()))
    assert back == s
    assert s.as_legacy() == (2, 5, "active+degraded", 10, 3, 7, True)


def test_mpgstats_v2_roundtrip_and_v1_decode():
    from ceph_tpu.msg.message import Message

    stats = [mkstat(), mkstat(ps=1, state="peering", primary=False)]
    msg = mm.MPGStats(3, 9, [s.as_legacy() for s in stats], 100, 200,
                      stats=stats, slow_ops=4, heartbeat_misses=11)
    back = Message.from_bytes(msg.to_bytes())
    assert back.osd == 3 and back.epoch == 9
    assert back.pgs == [s.as_legacy() for s in stats]
    assert back.stats == stats
    assert back.slow_ops == 4 and back.heartbeat_misses == 11
    # a pre-telemetry (v1) payload — no tail — decodes with defaults
    e = Encoder()
    e.s32(3).u32(9)
    e.seq([s.as_legacy() for s in stats], lambda en, p: (
        en.s64(p[0]), en.u32(p[1]), en.string(p[2]), en.u64(p[3]),
        en.u32(p[4]), en.u64(p[5]), en.u8(1 if p[6] else 0)))
    e.u64(100).u64(200)
    old = mm.MPGStats()
    old.decode_payload(Decoder(e.bytes()))
    assert old.pgs == [s.as_legacy() for s in stats]
    assert old.stats == [] and old.slow_ops == 0


# -- SnapshotRing -------------------------------------------------------------

def test_snapshot_ring_rate_and_delta():
    r = SnapshotRing()
    r.push({"ops": 0}, stamp=10.0)
    r.push({"ops": 50}, stamp=15.0)
    r.push({"ops": 100}, stamp=20.0)
    assert r.latest("ops") == 100
    # full-window rate over 10s: (100-0)/10
    assert r.rate("ops", window_s=60.0) == pytest.approx(10.0)
    # narrow window only sees the last hop: (100-50)/5
    assert r.rate("ops", window_s=5.0) == pytest.approx(10.0)
    assert r.delta("ops", window_s=60.0) == 100
    assert SnapshotRing().rate("ops") == 0.0  # no samples: no invention


# -- PGMap digest -------------------------------------------------------------

def _conf(**over):
    return Config({"mon_pg_stats_stale_s": 5.0,
                   "mon_pg_stuck_threshold": 10.0,
                   "mon_stats_rate_window": 60.0, **over})


def test_pgmap_digest_states_pools_and_rates():
    clk = Clock()
    pm = PGMapService(_conf(), now_fn=clk)
    pm.ingest(0, 1, [mkstat(ps=0, rec_ops=0),
                     mkstat(ps=1, state="active+degraded", degraded=5),
                     mkstat(pool=2, ps=0, n=3, nbytes=300)],
              used=50, total=100)
    clk.t += 2.0
    pm.ingest(0, 1, [mkstat(ps=0, cl_wr_ops=20, cl_wr_bytes=20480,
                            rec_ops=10, rec_bytes=10240),
                     mkstat(ps=1, state="active+degraded", degraded=5),
                     mkstat(pool=2, ps=0, n=3, nbytes=300)],
              used=50, total=100, slow_ops=2)
    d = pm.digest()
    assert d["pg_states"] == {"active": 2, "active+degraded": 1}
    assert d["num_pgs"] == 3
    assert d["pools"][1]["objects"] == 20
    assert d["pools"][2]["bytes"] == 300
    assert d["degraded_objects"] == 5
    assert d["slow_ops"] == {0: 2}
    # rates over the 2s between reports
    assert d["io"]["client_write_ops_per_s"] == pytest.approx(10.0)
    assert d["io"]["recovery_objects_per_s"] == pytest.approx(5.0)
    assert d["io"]["recovery_bytes_per_s"] == pytest.approx(5120.0)
    # replica rows never double-count the cluster totals
    pm.ingest(1, 1, [mkstat(ps=0, primary=False, cl_wr_ops=999)],
              used=0, total=0)
    assert pm.digest()["io"]["client_write_ops_per_s"] == \
        pytest.approx(10.0)


def test_pgmap_stuck_and_stale_and_heartbeat_views():
    clk = Clock()
    pm = PGMapService(_conf(), now_fn=clk)
    pm.ingest(0, 1, [mkstat(state="peering")], 0, 0,
              heartbeat_misses=0)
    clk.t += 4.0  # keep the report fresh (stale_s=5) across the poll
    pm.ingest(0, 1, [mkstat(state="peering")], 0, 0,
              heartbeat_misses=3)
    # state unchanged since the FIRST report: stuck_for spans both
    stuck = pm.stuck_pgs(threshold_s=3.0)
    assert len(stuck) == 1 and stuck[0]["state"] == "peering"
    assert stuck[0]["stuck_for_s"] == pytest.approx(4.0)
    # a state CHANGE resets the stuck clock
    pm.ingest(0, 1, [mkstat(state="active+degraded")], 0, 0)
    assert pm.stuck_pgs(threshold_s=3.0) == []
    # heartbeat misses grew between the two most recent reports
    assert pm.slow_heartbeat_osds() == []  # latest ingest reported 0 delta
    pm.ingest(0, 1, [mkstat()], 0, 0, heartbeat_misses=5)
    assert pm.slow_heartbeat_osds() == [0]
    # stale: the osd stops reporting
    clk.t += 20.0
    assert pm.stale_osds([0]) == [(0, pytest.approx(20.0))]
    assert pm.stale_osds([1]) == []  # never-reported osds don't count
    # stale reporters also stop feeding the digest
    assert pm.digest()["num_pgs"] == 0


def test_pgmap_degraded_ratio_uses_pool_width_and_clamps():
    clk = Clock()
    # width 3 (replicated size / EC k+m): the ratio denominator is
    # objects x width, so 2-of-3 holes reads 66.7%, never 200%
    pm = PGMapService(_conf(), now_fn=clk, pool_size_fn=lambda pid: 3)
    pm.ingest(0, 1, [mkstat(n=12, degraded=24,
                            state="active+degraded")], 0, 0)
    d = pm.digest()
    assert d["total_copies"] == 36
    assert d["degraded_ratio"] == pytest.approx(24 / 36, abs=1e-4)
    # no pool table: width falls back to 1 and the ratio clamps at 1.0
    pm2 = PGMapService(_conf(), now_fn=clk)
    pm2.ingest(0, 1, [mkstat(n=12, degraded=24,
                             state="active+degraded")], 0, 0)
    assert pm2.digest()["degraded_ratio"] == 1.0


def test_pgmap_replica_recovery_debt_visible_in_digest():
    """After a revive the missing copies live in the recovering
    REPLICA's own pg.missing — only its non-primary row carries them
    (the primary reads holes=0 once the peer is back up), so degraded
    must sum over every fresh report, not the primary-wins map."""
    clk = Clock()
    pm = PGMapService(_conf(), now_fn=clk)
    # primary: everyone up, nothing missing locally -> degraded=0
    pm.ingest(0, 1, [mkstat(ps=0, degraded=0)], 0, 0)
    # revived replica: still pulling 7 of its own objects
    pm.ingest(1, 1, [mkstat(ps=0, primary=False, degraded=7,
                            state="active+degraded")], 0, 0)
    d = pm.digest()
    assert d["degraded_objects"] == 7
    assert d["pools"][1]["degraded"] == 7
    # the replica finishes: the debt clears
    pm.ingest(1, 1, [mkstat(ps=0, primary=False, degraded=0)], 0, 0)
    assert pm.digest()["degraded_objects"] == 0


def test_pgmap_rates_decay_when_reports_stop():
    clk = Clock()
    pm = PGMapService(_conf(mon_stats_rate_window=5.0), now_fn=clk)
    pm.ingest(0, 1, [mkstat(cl_wr_ops=10)], 0, 0)
    clk.t += 2.0
    pm.ingest(0, 1, [mkstat(cl_wr_ops=10)], 0, 0)
    assert pm.digest()["io"]["client_write_ops_per_s"] == \
        pytest.approx(5.0)
    # every reporter goes silent past the window: the digest must read
    # 0, not serve the last rate forever off the stale ring tail
    clk.t += 20.0
    assert pm.digest()["io"]["client_write_ops_per_s"] == 0.0


def test_pgmap_replica_recovery_rate_feeds_digest():
    """Recovery io lands on whichever osd did the work (pull-based
    self-recovery) — a recovering REPLICA's rec_* deltas must feed the
    cluster recovery rate even though client io folds primary-only."""
    clk = Clock()
    pm = PGMapService(_conf(mon_stats_rate_window=10.0), now_fn=clk)
    pm.ingest(1, 1, [mkstat(primary=False)], 0, 0)
    clk.t += 2.0
    pm.ingest(1, 1, [mkstat(primary=False, rec_ops=10,
                            rec_bytes=10240, cl_wr_ops=999)], 0, 0)
    d = pm.digest()
    assert d["io"]["recovery_objects_per_s"] == pytest.approx(5.0)
    # the replica's client-io echo still never double-counts
    assert d["io"]["client_write_ops_per_s"] == 0.0


def test_pgmap_pg_rows_degraded_is_cross_report_sum():
    """The primary-wins row reads holes=0 the moment a dead peer is
    marked up; pg_rows (the ProgressModule/`pg dump` feed) must still
    show the replica's catch-up debt for the pg, or recovery events
    complete at revive while objects are still being pulled."""
    clk = Clock()
    pm = PGMapService(_conf(), now_fn=clk)
    pm.ingest(0, 1, [mkstat(ps=0, degraded=0)], 0, 0)
    pm.ingest(1, 1, [mkstat(ps=0, primary=False, degraded=7,
                            state="active+degraded")], 0, 0)
    (row,) = pm.pg_rows(fresh_only=True)
    assert row["primary"] is True and row["degraded"] == 7
    # debt drains with the replica's next report
    pm.ingest(1, 1, [mkstat(ps=0, primary=False, degraded=0)], 0, 0)
    (row,) = pm.pg_rows(fresh_only=True)
    assert row["degraded"] == 0


def test_pgmap_down_reporter_testimony_is_void():
    """A down-marked osd's last report stays 'fresh' for stale_s, but
    counting its missing-set alongside the primary's new acting-set
    holes would double-count the debt; its statfs capacity is gone
    too."""
    clk = Clock()
    up = {0: True, 1: True}
    pm = PGMapService(_conf(), now_fn=clk,
                      osd_up_fn=lambda o: up.get(o, False))
    pm.ingest(0, 1, [mkstat(ps=0, degraded=0)], used=10, total=100)
    pm.ingest(1, 1, [mkstat(ps=0, primary=False, degraded=50,
                            state="active+degraded")], used=10,
              total=100)
    assert pm.digest()["degraded_objects"] == 50
    assert pm.digest()["total_bytes"] == 200
    # osd.1 dies mid-recovery; the primary now counts its hole
    up[1] = False
    pm.ingest(0, 1, [mkstat(ps=0, degraded=100,
                            state="active+degraded")], used=10,
              total=100)
    d = pm.digest()
    assert d["degraded_objects"] == 100  # not 150
    assert d["total_bytes"] == 100       # dead capacity gone


def test_pgmap_active_degraded_is_not_stuck():
    clk = Clock()
    pm = PGMapService(_conf(), now_fn=clk)
    pm.ingest(0, 1, [mkstat(state="active+degraded", degraded=5),
                     mkstat(ps=1, state="peering")], 0, 0)
    clk.t += 4.0
    pm.ingest(0, 1, [mkstat(state="active+degraded", degraded=5),
                     mkstat(ps=1, state="peering")], 0, 0)
    stuck = pm.stuck_pgs(threshold_s=3.0)
    # a long recovery serves io — only the truly non-active pg sticks
    assert [r["state"] for r in stuck] == ["peering"]


def test_pgmap_first_report_heartbeat_history_not_growth():
    """A cumulative heartbeat_misses total arriving in an OSD's FIRST
    report (mon restart / leader failover) is history, not live
    growth: no spurious OSD_SLOW_HEARTBEAT flash."""
    clk = Clock()
    pm = PGMapService(_conf(), now_fn=clk)
    pm.ingest(0, 1, [mkstat()], 0, 0, heartbeat_misses=11)
    assert pm.slow_heartbeat_osds() == []
    # growth between two reports IS live evidence
    pm.ingest(0, 1, [mkstat()], 0, 0, heartbeat_misses=12)
    assert pm.slow_heartbeat_osds() == [0]


# -- health checks ------------------------------------------------------------

def make_mon():
    from tests.test_mon_services import make_solo_mon

    return make_solo_mon()


def test_health_checks_from_pgmap_feed():
    mon = make_mon()
    clk = Clock()
    mon.pgmap = PGMapService(mon.ctx.conf, now_fn=clk)
    mon.ctx.conf.set_val("mon_pg_stuck_threshold", 3.0)
    mon.pgmap.ingest(0, 1, [
        mkstat(ps=0, state="active+degraded", degraded=4, n=10),
        mkstat(ps=1, state="peering"),
        mkstat(ps=2, unfound=1)], 0, 0, slow_ops=3)
    _status, checks = mon.services["health"].gather()
    assert checks["PG_DEGRADED"]["summary"] == "1 pgs degraded"
    assert "PG_PEERING" in checks
    assert "OBJECT_DEGRADED" in checks
    assert "4/" in checks["OBJECT_DEGRADED"]["summary"]
    assert checks["OBJECT_UNFOUND"]["severity"] == "HEALTH_ERR"
    # SLOW_OPS names the daemon
    assert any("osd.0" in line for line in checks["SLOW_OPS"]["detail"])
    # stuck fires once the unchanged state outlives the threshold
    clk.t += 4.0
    mon.pgmap.ingest(0, 1, [
        mkstat(ps=1, state="peering")], 0, 0)
    _status, checks = mon.services["health"].gather()
    assert "PG_STUCK" in checks
    assert any("peering" in d for d in checks["PG_STUCK"]["detail"])


def test_digest_scrub_errors_and_pg_damaged_check():
    """PGStat scrub_errors (the v2 tail) aggregates into the digest
    and raises PG_DAMAGED (ERR) naming the pgs; clears when the stats
    report clean again."""
    mon = make_mon()
    clk = Clock()
    mon.pgmap = PGMapService(mon.ctx.conf, now_fn=clk)
    mon.pgmap.ingest(0, 1, [
        mkstat(ps=0, scrub_errors=2, last_scrub=900.0,
               last_deep_scrub=900.0),
        mkstat(ps=1)], 0, 0)
    d = mon.pgmap.digest()
    assert d["scrub_errors"] == 2 and d["damaged_pgs"] == 1
    _status, checks = mon.services["health"].gather()
    assert checks["PG_DAMAGED"]["severity"] == "HEALTH_ERR"
    assert "2 scrub errors" in checks["PG_DAMAGED"]["summary"]
    assert any("1.0" in line for line in checks["PG_DAMAGED"]["detail"])
    # a replica's row must not double-count (primary rows only)
    mon.pgmap.ingest(1, 1, [
        mkstat(ps=0, primary=False, scrub_errors=2)], 0, 0)
    assert mon.pgmap.digest()["scrub_errors"] == 2
    # repaired: the next report clears the check
    mon.pgmap.ingest(0, 1, [
        mkstat(ps=0, scrub_errors=0, last_scrub=950.0,
               last_deep_scrub=950.0),
        mkstat(ps=1)], 0, 0)
    assert mon.pgmap.digest()["scrub_errors"] == 0
    _status, checks = mon.services["health"].gather()
    assert "PG_DAMAGED" not in checks
    # pg_rows carry the scrub fields for dump consumers
    row = next(r for r in mon.pgmap.pg_rows() if r["pgid"] == "1.0")
    assert row["last_deep_scrub"] == 950.0
    assert row["scrub_errors"] == 0


def test_not_deep_scrubbed_view_and_check():
    """PG_NOT_DEEP_SCRUBBED: disabled at the conf default, raises for
    primary PGs with old/never deep-scrub stamps once armed, clears
    when the stamps refresh."""
    mon = make_mon()
    clk = Clock(t=10000.0)
    mon.pgmap = PGMapService(mon.ctx.conf, now_fn=clk)
    mon.pgmap.ingest(0, 1, [
        mkstat(ps=0, last_deep_scrub=0.0),          # never
        mkstat(ps=1, last_deep_scrub=9995.0),        # fresh
        mkstat(ps=2, last_deep_scrub=9000.0),        # old
        mkstat(ps=3, primary=False,
               last_deep_scrub=0.0)], 0, 0)         # replica: ignored
    assert mon.pgmap.not_deep_scrubbed() == []  # conf default 0 = off
    _status, checks = mon.services["health"].gather()
    assert "PG_NOT_DEEP_SCRUBBED" not in checks
    mon.ctx.conf.set_val("mon_warn_not_deep_scrubbed_s", 100.0)
    rows = mon.pgmap.not_deep_scrubbed()
    assert {r["pgid"] for r in rows} == {"1.0", "1.2"}
    assert next(r for r in rows
                if r["pgid"] == "1.0")["age_s"] is None  # never
    _status, checks = mon.services["health"].gather()
    assert checks["PG_NOT_DEEP_SCRUBBED"]["severity"] == "HEALTH_WARN"
    assert "2 pgs" in checks["PG_NOT_DEEP_SCRUBBED"]["summary"]
    assert any("never" in d
               for d in checks["PG_NOT_DEEP_SCRUBBED"]["detail"])
    # deep scrubs land: the stamps refresh and the check clears
    mon.pgmap.ingest(0, 1, [
        mkstat(ps=0, last_deep_scrub=9990.0),
        mkstat(ps=1, last_deep_scrub=9995.0),
        mkstat(ps=2, last_deep_scrub=9990.0)], 0, 0)
    assert mon.pgmap.not_deep_scrubbed() == []
    _status, checks = mon.services["health"].gather()
    assert "PG_NOT_DEEP_SCRUBBED" not in checks


def test_health_stale_report_check_and_conf_cutoff():
    mon = make_mon()
    clk = Clock()
    mon.pgmap = PGMapService(mon.ctx.conf, now_fn=clk)
    mon.pgmap.ingest(1, 1, [
        mkstat(state="active+degraded", degraded=2)], 0, 0)
    _status, checks = mon.services["health"].gather()
    assert "PG_DEGRADED" in checks
    # reports go stale (conf-driven cutoff, default 30s): the degraded
    # pg vanishes from the digest but the staleness is its own WARN —
    # a live osd with stale stats must not read HEALTH_OK
    clk.t += 31.0
    status, checks = mon.services["health"].gather()
    assert "PG_DEGRADED" not in checks
    assert "MON_STALE_PG_REPORTS" in checks
    assert "osd.1" in checks["MON_STALE_PG_REPORTS"]["detail"][0]
    assert status == "HEALTH_WARN"
    # widen the cutoff at runtime: the report is fresh again
    mon.ctx.conf.set_val("mon_pg_stats_stale_s", 120.0)
    _status, checks = mon.services["health"].gather()
    assert "MON_STALE_PG_REPORTS" not in checks
    assert "PG_DEGRADED" in checks


def test_health_mute_suppresses_status_but_lists_in_detail():
    mon = make_mon()
    clk = Clock()
    mon.pgmap = PGMapService(mon.ctx.conf, now_fn=clk)
    mon.pgmap.ingest(0, 1, [mkstat(state="active+degraded",
                                   degraded=1)], 0, 0)
    code, out = mon._do_command({"prefix": "health"})
    assert out["status"] == "HEALTH_WARN"
    mon._do_command({"prefix": "health mute", "check": "PG_DEGRADED"})
    mon._do_command({"prefix": "health mute",
                     "check": "OBJECT_DEGRADED"})
    _code, out = mon._do_command({"prefix": "health"})
    # muted checks no longer drive the overall status...
    assert out["status"] == "HEALTH_OK"
    # ...but health detail still lists them, flagged muted
    _code, det = mon._do_command({"prefix": "health detail"})
    assert det["checks"]["PG_DEGRADED"]["muted"] is True
    assert det["status"] == "HEALTH_OK"
    assert "PG_DEGRADED" in det["muted"] or \
        "PG_DEGRADED" in out["muted"]
    # unmute: the WARN returns
    mon._do_command({"prefix": "health unmute", "check": "PG_DEGRADED"})
    _code, out = mon._do_command({"prefix": "health"})
    assert out["status"] == "HEALTH_WARN"
    _code, det = mon._do_command({"prefix": "health detail"})
    assert det["checks"]["PG_DEGRADED"]["muted"] is False


def test_health_transitions_land_in_cluster_log():
    mon = make_mon()
    clk = Clock()
    mon.pgmap = PGMapService(mon.ctx.conf, now_fn=clk)
    health = mon.services["health"]
    health.tick()  # HEALTH_OK baseline: no transition, nothing logged
    assert all("cluster health" not in e["msg"]
               for e in mon.services["logm"].entries)
    mon.pgmap.ingest(0, 1, [mkstat(state="active+degraded",
                                   degraded=2)], 0, 0)
    health.tick()
    msgs = [e["msg"] for e in mon.services["logm"].entries]
    assert any("HEALTH_OK -> HEALTH_WARN" in m for m in msgs)
    assert any("PG_DEGRADED" in m and "raised" in m for m in msgs)
    # recovery completes: the WARN clears and the edge is logged
    mon.pgmap.ingest(0, 1, [mkstat(state="active")], 0, 0)
    health.tick()
    msgs = [e["msg"] for e in mon.services["logm"].entries]
    assert any("HEALTH_WARN -> HEALTH_OK" in m for m in msgs)
    assert any("PG_DEGRADED" in m and "cleared" in m for m in msgs)


# -- optracker slow depth -----------------------------------------------------

def test_slow_depth_counts_live_and_recent_then_ages_out():
    from ceph_tpu.core.optracker import OpTracker

    trk = OpTracker(slow_op_threshold=0.0)  # everything counts as slow
    op = trk.create_op("op1")
    assert trk.slow_depth(30.0) == 1  # in-flight past threshold
    op.finish(stage="commit_sent")
    assert trk.slow_depth(30.0) == 1  # fresh ring entry
    # age the ring entry past the window: the health signal decays
    # while the dumpable evidence stays
    op.done_at -= 100.0
    assert trk.slow_depth(30.0) == 0
    assert trk.dump_slow()["num_ops"] == 1


# -- prometheus exposition ----------------------------------------------------

_SAMPLE_RE = re.compile(
    r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{[^}]*\})?\s+(\S+)$")


def parse_exposition(text):
    """Minimal exposition-format parser: TYPE table + samples; raises
    on any line that is not a comment, blank, or valid sample."""
    types, samples = {}, []
    for line in text.splitlines():
        if not line.strip():
            continue
        if line.startswith("# TYPE "):
            _h, _t, name, typ = line.split()
            types[name] = typ
            continue
        if line.startswith("#"):
            continue
        m = _SAMPLE_RE.match(line)
        assert m, f"unparseable exposition line: {line!r}"
        labels = {}
        if m.group(2):
            for part in m.group(2)[1:-1].split(","):
                if part:
                    k, v = part.split("=", 1)
                    labels[k] = v.strip('"')
        samples.append((m.group(1), labels, m.group(3)))
    return types, samples


def _mgr_with_feeds():
    from ceph_tpu.mgr.manager import MgrDaemon

    ctx = Context("test.prom", {})
    pc = ctx.perf.create("osd.0.op")
    pc.add_histogram("lat_test_us")
    for v in (3, 100, 4000, 4001, 70000):
        pc.hinc("lat_test_us", v)
    pc.add_u64_counter("op_w")
    pc.inc("op_w", 42)
    mgr = MgrDaemon(ctx)
    mgr.register_daemon("osd.0", ctx)
    clk = Clock()
    pm = PGMapService(_conf(), now_fn=clk)
    pm.ingest(0, 1, [mkstat(n=10, nbytes=1234),
                     mkstat(ps=1, state="active+degraded", degraded=3)],
              used=10, total=100)
    mgr.pgmap_digest_fn = pm.digest
    mgr.health_fn = lambda: ("HEALTH_WARN", {
        "PG_DEGRADED": {"severity": "HEALTH_WARN",
                        "summary": "1 pgs degraded", "detail": []}})
    return mgr


def test_prometheus_export_roundtrips_and_has_inf_bucket():
    mgr = _mgr_with_feeds()
    body = mgr.modules["prometheus"].export()
    types, samples = parse_exposition(body)  # every line must parse
    by_name = {}
    for name, labels, val in samples:
        by_name.setdefault(name, []).append((labels, val))
    # histogram exposition: finite le buckets cumulative + mandatory
    # terminal +Inf equal to _count (absent before this fix)
    hist = "ceph_osd_0_op_lat_test_us"
    assert types[hist] == "histogram"
    buckets = by_name[hist + "_bucket"]
    les = [lab["le"] for lab, _v in buckets]
    assert les[-1] == "+Inf"
    finite = [(float(lab["le"]), float(v)) for lab, v in buckets
              if lab["le"] != "+Inf"]
    assert finite == sorted(finite)  # monotone cumulative, ordered les
    assert all(b <= 5 for _le, b in finite)
    count = float(by_name[hist + "_count"][0][1])
    inf_val = float(buckets[-1][1])
    assert inf_val == count == 5
    # the le labels are µs powers of two: 70000us lands under le=2^17
    assert finite[-1][0] == 131072.0
    # plain counter round-trips
    assert float(by_name["ceph_osd_0_op_op_w"][0][1]) == 42
    # cluster gauges: health, pg states, per-pool df
    assert float(by_name["ceph_health_status"][0][1]) == 1
    states = {lab["state"]: float(v)
              for lab, v in by_name["ceph_pg_state"]}
    assert states["active+degraded"] == 1 and states["total"] == 2
    pools = {lab["pool"]: float(v)
             for lab, v in by_name["ceph_pool_objects"]}
    assert pools["1"] == 20
    assert float(by_name["ceph_cluster_degraded_objects"][0][1]) == 3


# -- progress module ----------------------------------------------------------

def test_progress_eta_converges_monotonically():
    from ceph_tpu.mgr.manager import MgrDaemon

    mgr = MgrDaemon(Context("test.prog", {}))
    prog = mgr.modules["progress"]
    clk = Clock(0.0)
    prog._now = clk
    degraded = {"v": 100}
    mgr.pg_rows_fn = lambda: [{"pgid": "1.0", "primary": True,
                               "degraded": degraded["v"]}]
    prog.refresh()
    (ev,) = prog.events.values()
    assert ev["baseline"] == 100 and ev["eta_s"] is None
    # linear recovery, 10 objects/s: ETA tracks remaining/rate and the
    # published value never increases (convergence from above)
    etas = []
    for t, remaining in ((2.0, 80), (4.0, 60), (6.0, 40), (8.0, 20)):
        clk.t = t
        degraded["v"] = remaining
        prog.refresh()
        etas.append(prog.events["recovery-1.0"]["eta_s"])
    assert etas == sorted(etas, reverse=True)
    assert etas[-1] == pytest.approx(2.0)  # 20 left at 10/s
    assert prog.events["recovery-1.0"]["progress"] == pytest.approx(0.8)
    # completion: the event moves to the completed ring with its
    # measured duration — the ETA-error ground truth
    clk.t = 10.0
    degraded["v"] = 0
    code, out = prog.handle_command({"prefix": "progress"})
    assert code == 0 and out["events"] == []
    (done,) = out["completed"]
    assert done["duration_s"] == pytest.approx(10.0)
    assert done["progress"] == 1.0


def test_progress_repair_events_track_scrub_errors():
    """A primary row reporting scrub_errors opens a repair progress
    event; the event completes (with measured duration) when the PG's
    report reads clean again — and repair events never complete
    against the RECOVERY completion rule (disjoint id namespaces)."""
    from ceph_tpu.mgr.manager import MgrDaemon

    mgr = MgrDaemon(Context("test.repair_prog", {}))
    prog = mgr.modules["progress"]
    clk = Clock(0.0)
    prog._now = clk
    errs = {"v": 3}
    mgr.pg_rows_fn = lambda: [{"pgid": "2.1", "primary": True,
                               "degraded": 0,
                               "scrub_errors": errs["v"]}]
    prog.refresh()
    ev = prog.events["repair-2.1"]
    assert ev["baseline"] == 3 and "Repairing" in ev["message"]
    # partially repaired: progress advances, the event stays open
    clk.t = 2.0
    errs["v"] = 1
    prog.refresh()
    assert prog.events["repair-2.1"]["progress"] == \
        pytest.approx(2 / 3, abs=1e-3)
    # clean report: completes with the measured duration
    clk.t = 5.0
    errs["v"] = 0
    code, out = prog.handle_command({"prefix": "progress"})
    assert code == 0 and out["events"] == []
    (done,) = out["completed"]
    assert done["id"] == "repair-2.1"
    assert done["duration_s"] == pytest.approx(5.0)
    assert done["progress"] == 1.0


# -- device-visibility gauges -------------------------------------------------

def test_tpuq_gauges_sampled():
    from ceph_tpu.ec import codec_from_profile
    from ceph_tpu.tpu.queue import StripeBatchQueue

    q = StripeBatchQueue()
    codec = codec_from_profile("plugin=isa k=2 m=1 "
                               "technique=reed_sol_van")
    q.sample()
    q.encode(codec, np.zeros((2, 1024), dtype=np.uint8))
    q.sample()
    dump = q.perf.dump()
    assert "queue_depth" in dump and "device_busy_pct" in dump
    assert dump["staging_slots_used"] == 0
    # host clock around the batch: it moves with every batch, and is
    # not what the gauge reads (the CPU's native encode waits on no
    # device: busy 0 %)
    assert q.device_time_s > 0.0
    assert dump["device_busy_pct"] == 0
    q.stop()


def test_device_busy_gauge_follows_time_blocked_on_the_device():
    """`device_busy_pct` is the rate of devwatch's `dev.wait` seconds
    (host threads blocked in fetch()), not of the batches' host clock,
    which at saturation grows a second a second whatever the device
    does."""
    import time

    from ceph_tpu.tpu import devwatch
    from ceph_tpu.tpu.queue import StripeBatchQueue

    q = StripeBatchQueue()
    q.sample()
    time.sleep(0.05)
    q.device_time_s += 0.05           # a saturated worker's host clock
    q.sample()
    assert q.perf.dump()["device_busy_pct"] == 0
    t0 = time.monotonic()
    devwatch.fetch(np.zeros(4))       # a dev.wait span, however short
    before = devwatch.watch().wait_s
    time.sleep(0.05)
    devwatch.watch().note_wait(0.04)  # 40 ms blocked since then
    assert devwatch.watch().wait_s == pytest.approx(before + 0.04)
    q.sample()
    busy = q.perf.dump()["device_busy_pct"]
    assert 0.04 / (time.monotonic() - t0 + 0.1) * 100 - 1 <= busy <= 100
    assert busy >= 10
