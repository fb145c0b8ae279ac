"""VStartCluster + rados CLI tests (reference src/vstart.sh +
src/tools/rados; the "a user can drive the whole thing" surface).
"""

import contextlib
import io
import os
import sys

import pytest

TOOLS = os.path.join(os.path.dirname(__file__), "..", "tools")
sys.path.insert(0, os.path.abspath(TOOLS))


def _capture(fn, argv):
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = fn(argv)
    return rc, buf.getvalue()


def test_vstart_pool_io_and_listing():
    from ceph_tpu.vstart import VStartCluster

    with VStartCluster(n_mons=1, n_osds=3) as c:
        pool = c.create_pool("data", size=2)
        io_ = c.client().ioctx(pool)
        io_.write_full("alpha", b"A" * 1000)
        io_.write_full("beta", b"B" * 10)
        assert io_.read("alpha") == b"A" * 1000
        assert io_.list_objects() == ["alpha", "beta"]
        io_.remove("beta")
        assert io_.list_objects() == ["alpha"]
        # under heavy host load an OSD can transiently miss its 3s
        # heartbeat grace and be reported down; health converges back
        # once scheduling recovers — poll instead of a one-shot assert
        import time as _time

        deadline = _time.time() + 20
        while True:
            code, out = c.command({"prefix": "health"})
            if code == 0 and out["status"] == "HEALTH_OK":
                break
            assert _time.time() < deadline, f"health never OK: {out}"
            _time.sleep(0.5)


def test_vstart_survives_osd_kill():
    from ceph_tpu.vstart import VStartCluster

    with VStartCluster(n_mons=1, n_osds=4) as c:
        pool = c.create_pool("r3", size=3)
        io_ = c.client().ioctx(pool)
        io_.write_full("obj", b"payload" * 100)
        victim = None
        m = c.leader().osdmap
        pgid = m.object_to_pg(pool, "obj")
        _up, _upp, acting, _ap = m.pg_to_up_acting(pgid)
        victim = acting[0]
        c.kill_osd(victim)

        def remapped():
            mm = c.leader().osdmap
            _u, _up2, act, _a = mm.pg_to_up_acting(pgid)
            return victim not in act and all(a >= 0 for a in act[:2])

        c.wait_for(remapped, what="remap after kill")
        assert io_.read("obj") == b"payload" * 100


def test_vstart_durable_dir_remount(tmp_path):
    from ceph_tpu.vstart import VStartCluster

    d = str(tmp_path / "cluster")
    with VStartCluster(n_mons=1, n_osds=2, data_dir=d) as c:
        pool = c.create_pool("keep", size=2)
        c.client().ioctx(pool).write_full("persist", b"still here")
    # fresh cluster over the same stores: object data survives (mon
    # state is fresh, so recreate the pool with the same id ordering)
    with VStartCluster(n_mons=1, n_osds=2, data_dir=d) as c2:
        pool2 = c2.create_pool("keep", size=2)
        io2 = c2.client().ioctx(pool2)
        assert io2.read("persist") == b"still here"


def test_rados_cli_script():
    import rados as rados_cli
    import tempfile

    with tempfile.NamedTemporaryFile(delete=False) as f:
        f.write(b"cli-payload")
        path = f.name
    rc, out = _capture(rados_cli.main, [
        "--vstart", "1x3", "--pool", "cli", "--pool-size", "2",
        "--script",
        f"mkpool cli; put a {path}; stat a; ls; df",
    ])
    assert rc == 0
    lines = out.strip().splitlines()
    assert lines[0].startswith("pool cli id ")
    assert "a size 11" in out
    assert "osds: 3/3 up" in out
    os.unlink(path)


def test_ceph_admin_cli_script():
    import ceph as ceph_cli
    import json

    rc, out = _capture(ceph_cli.main, [
        "--vstart", "1x3", "--script",
        "status; health; osd tree; config set global debug 5; "
        "config get osd.1; log cli smoke; log last 5; mon dump",
    ])
    assert rc == 0
    docs = []
    depth = 0
    buf = ""
    for line in out.splitlines():  # split the concatenated json docs
        buf += line + "\n"
        depth += line.count("{") - line.count("}")
        if depth == 0 and buf.strip():
            docs.append(json.loads(buf))
            buf = ""
    status, health, tree, cset, cget, logw, loglast, mondump = docs
    assert status["rc"] == 0 and status["num_up_osds"] == 3
    assert health["status"] == "HEALTH_OK"
    assert any(n["name"] == "osd.2" for n in tree["nodes"])
    assert any(n.get("type") for n in tree["nodes"])
    assert cget["config"]["debug"] == "5"  # global applies to osd.1
    assert loglast["lines"][-1]["msg"] == "cli smoke"
    assert mondump["monmap"]["epoch"] >= 1


def test_ceph_cli_osd_down_and_cephx():
    import ceph as ceph_cli
    import json

    rc, out = _capture(ceph_cli.main, [
        "--vstart", "1x3", "--cephx", "--script",
        "auth get-or-create client.app; auth ls; osd out 1; health",
    ])
    assert rc == 0
    docs = [json.loads(d) for d in
            out.replace("}\n{", "}\x00{").split("\x00")]
    create, ls, _out_cmd, health = docs
    assert len(bytes.fromhex(create["key"])) == 32
    assert "client.app" in ls["entities"]
    assert health["status"] == "HEALTH_WARN"  # osd.1 out
    assert "OSD_OUT" in health["checks"]


def test_rbd_cli_lifecycle(tmp_path):
    import rbd as rbd_cli

    payload = os.urandom(300_000)
    src = tmp_path / "disk.img"
    src.write_bytes(payload)
    out_path = tmp_path / "out.img"
    rc, out = _capture(rbd_cli.main, [
        "--vstart", "1x3", "--script",
        f"import {src} vol1; ls; info vol1; "
        f"create vol2 1m; journal-replay vol1 vol2; "
        f"export vol1 {out_path}; resize vol1 64k; info vol1; rm vol2; ls",
    ])
    assert rc == 0
    assert out_path.read_bytes() == payload
    assert "vol1" in out and "vol2" in out
    assert "size 65536 bytes" in out  # post-resize info
    # final ls shows only vol1
    assert out.strip().splitlines()[-1] == "vol1"


def test_vstart_blockstore_backed_cluster(tmp_path):
    """The BlueStore-role BlockStore under the FULL daemon stack:
    writes through mons+osds, durable across cluster restart, fsck
    clean."""
    from ceph_tpu.vstart import VStartCluster

    d = str(tmp_path / "bs-cluster")
    with VStartCluster(n_mons=1, n_osds=2, data_dir=d,
                       store_kind="blockstore") as c:
        pool = c.create_pool("bs", size=2)
        io_ = c.client().ioctx(pool)
        io_.write_full("obj", b"block-backed" * 500)
    with VStartCluster(n_mons=1, n_osds=2, data_dir=d,
                       store_kind="blockstore") as c2:
        pool2 = c2.create_pool("bs", size=2)
        io2 = c2.client().ioctx(pool2)
        assert io2.read("obj") == b"block-backed" * 500
        for o in c2.osds.values():
            assert o.store.fsck() == []


def test_cephfs_shell_cli(tmp_path):
    import cephfs_shell

    src = tmp_path / "hello.txt"
    src.write_bytes(b"fs payload")
    rc, out = _capture(cephfs_shell.main, [
        "--vstart", "1x3", "--script",
        f"mkdir /docs; put {src} /docs/hello.txt; stat /docs/hello.txt; "
        "mv /docs/hello.txt /docs/renamed.txt; ls /docs; tree /; "
        "cat /docs/renamed.txt; rm /docs/renamed.txt; rmdir /docs; ls /",
    ])
    assert rc == 0
    assert "size 10" in out
    assert "renamed.txt" in out
    assert "d docs" in out
    assert "fs payload" in out
    assert out.strip().splitlines()[-1] != "docs"  # rmdir removed it


def test_pg_dump_and_pg_health():
    """MPGStats feed: `pg dump` shows every PG active with object
    counts; killing an OSD surfaces PG_DEGRADED in health."""
    from ceph_tpu.vstart import VStartCluster

    with VStartCluster(n_mons=1, n_osds=3,
                       conf={"osd_pg_stats_interval": 0.5}) as c:
        pool = c.create_pool("stats", size=3, pg_num=4)
        io = c.client().ioctx(pool)
        for i in range(8):
            io.write_full(f"s{i}", b"x" * 100)

        def dumped():
            code, out = c.command({"prefix": "pg dump"})
            if code != 0 or out["num_pg_stats"] < 4:
                return False
            rows = [r for r in out["pg_stats"]
                    if r["pgid"].startswith(f"{pool}.")]
            return (len(rows) == 4
                    and all(r["state"] == "active" for r in rows)
                    and sum(r["num_objects"] for r in rows) == 8)

        c.wait_for(dumped, what="pg dump active + counts")
        c.kill_osd(2)

        def degraded():
            code, out = c.command({"prefix": "health"})
            return code == 0 and "PG_DEGRADED" in out["checks"]

        c.wait_for(degraded, timeout=30.0, what="PG_DEGRADED")


def test_osd_fullness_health():
    """ObjectStore::statfs feeds OSD_NEARFULL/OSD_FULL health via the
    MPGStats reports (reference nearfull/full ratios)."""
    from ceph_tpu.vstart import VStartCluster

    with VStartCluster(n_mons=1, n_osds=2,
                       conf={"osd_pg_stats_interval": 0.3}) as c:
        pool = c.create_pool("full", size=2)
        io = c.client().ioctx(pool)
        io.write_full("x", b"d" * 4096)

        def reported():
            ld = c.leader()
            return (len(ld.osd_fullness) == 2
                    and all(t > 0 for _u, t in ld.osd_fullness.values()))

        c.wait_for(reported, what="fullness reports")
        code, out = c.command({"prefix": "health"})
        assert code == 0
        assert "OSD_NEARFULL" not in out["checks"]  # MemStore ~empty
        # inject a near-full report directly (the wire path is proven
        # above; the ratio->check logic is what's under test here).
        # Stop the daemons first so live reports can't overwrite it.
        for i in list(c.osds):
            c.kill_osd(i)
        ld = c.leader()
        with ld.lock:
            ld.osd_fullness[0] = (90 << 20, 100 << 20)  # 90%
            ld.osd_fullness[1] = (96 << 20, 100 << 20)  # 96%
        code, out = c.command({"prefix": "health"})
        assert "OSD_NEARFULL" in out["checks"]
        assert "OSD_FULL" in out["checks"]
        assert out["status"] == "HEALTH_ERR"


def test_osd_df_and_status_pg_states():
    from ceph_tpu.vstart import VStartCluster

    with VStartCluster(n_mons=1, n_osds=2,
                       conf={"osd_pg_stats_interval": 0.3}) as c:
        pool = c.create_pool("dfp", size=2, pg_num=4)
        io = c.client().ioctx(pool)
        io.write_full("a", b"z" * 1000)

        def ready():
            code, out = c.command({"prefix": "osd df"})
            if code != 0 or len(out["nodes"]) != 2:
                return False
            code, st = c.command({"prefix": "status"})
            return (code == 0
                    and st["pg_states"].get("active", 0) >= 4)

        c.wait_for(ready, what="osd df + pg states")
        code, out = c.command({"prefix": "osd df"})
        assert all(n["total_bytes"] > 0 for n in out["nodes"])


def test_osd_reasserts_itself_when_the_map_holds_another_address():
    """A map that shows this OSD up at an address that is not its own
    (a restarted cluster's durable mon holds the previous incarnation)
    must make it boot again: it used to look only at the up bit, and
    when the stale map arrived before its first boot it never
    announced itself — ops to its PGs then timed out after 30 s."""
    from ceph_tpu.vstart import VStartCluster

    with VStartCluster(n_mons=1, n_osds=2) as c:
        mon = c.leader()
        own = tuple(c.osds[0].msgr.addr)
        with mon.lock:
            mon._mutate_map(
                lambda nm: nm.osd_addrs.__setitem__(0, ("127.0.0.1", 1)))
        c.wait_for(
            lambda: tuple(c.leader().osdmap.osd_addrs.get(0, ())) == own,
            timeout=10.0, what="osd.0 registered its own address again")
        pool = c.create_pool("re", size=2)
        io_ = c.client().ioctx(pool)
        io_.write_full("obj", b"x" * 100)
        assert io_.read("obj") == b"x" * 100
