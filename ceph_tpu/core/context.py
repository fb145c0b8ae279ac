"""Context — the per-process service bundle (CephContext equivalent).

Reference: CephContext/g_ceph_context (src/common/ceph_context.h) as
created by global_init (src/global/global_init.h:34): owns the config,
the log, the perf-counter collection, the admin socket, and the
heartbeat map, and hands them to every subsystem.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

from ceph_tpu.core.admin_socket import AdminSocket
from ceph_tpu.core.config import Config
from ceph_tpu.core.heartbeat import HeartbeatMap
from ceph_tpu.core.log import Log
from ceph_tpu.core.perf import PerfCountersCollection


class Context:
    def __init__(
        self,
        name: str = "client.admin",
        overrides: Optional[Dict[str, Any]] = None,
    ) -> None:
        overrides = dict(overrides or {})
        overrides.setdefault("name", name)
        self.conf = Config(overrides)
        self.name = self.conf.get("name")
        self.log = Log(
            default_level=self.conf.get("log_level"),
            ring_size=self.conf.get("log_ring_size"),
            name=self.name,
        )
        self.perf = PerfCountersCollection()
        self.heartbeat = HeartbeatMap()
        from ceph_tpu.core.tracing import Tracer

        self.trace = Tracer(self.name,
                            enabled=bool(self.conf.get("tracing")))
        self.admin: Optional[AdminSocket] = None
        path = self.conf.get("admin_socket")
        if path:
            self._start_admin(path)
        self.conf.add_observer(
            ("log_level",),
            lambda _n, v: [self.log.set_level(s, v) for s in self.log._levels],
        )

    def _start_admin(self, path: str) -> None:
        a = AdminSocket(path)
        a.register("perf dump", lambda c: self.perf.dump(),
                   "dump perf counters")
        a.register("config get",
                   lambda c: {c["key"]: self.conf.get(c["key"])},
                   "get one config value")
        a.register("config set",
                   lambda c: (self.conf.set_val(c["key"], c["value"]),
                              {"success": True})[1],
                   "set a config value at runtime")
        a.register("config diff", lambda c: self.conf.diff(),
                   "non-default config values")
        a.register("log dump", lambda c: self.log.dump_recent(
            int(c.get("count", 1000))), "recent in-memory log events")
        a.register("health", lambda c: {
            "healthy": self.heartbeat.is_healthy(),
            "unhealthy_workers": self.heartbeat.unhealthy_workers(),
        }, "thread liveness")
        def _dump_trace(c):
            if "trace_id" in c:
                return self.trace.dump(int(str(c["trace_id"]), 16))
            from ceph_tpu.core.tracing import recorder

            return recorder().dump(int(c.get("count", 1000)))

        a.register("dump_trace", _dump_trace,
                   "the process's one span recorder: the ring's tail "
                   "(stage spans, op timelines, the overwritten count; "
                   "count=<n>), or with trace_id=<hex> the blkin spans "
                   "of one trace")

        def _device_dump(c):
            # process-wide like the StripeBatchQueue: one device
            # runtime per process, one compile table
            from ceph_tpu.tpu.devwatch import watch

            return watch().dump()

        a.register("device compile dump", _device_dump,
                   "per-kernel-family XLA compile table: compiles, "
                   "wall seconds, distinct shape signatures, cache "
                   "hits, recent storms and events")
        a.start()
        self.admin = a

    def shutdown(self) -> None:
        if self.admin is not None:
            self.admin.stop()
            self.admin = None


def global_init(
    name: str, overrides: Optional[Dict[str, Any]] = None, argv=None
):
    """Config-parse + context construction (global_init equivalent)."""
    ctx = Context(name, overrides)
    rest = ctx.conf.parse_argv(argv) if argv else []
    ctx.conf.startup_done()  # non-runtime options frozen from here on
    return ctx, rest
