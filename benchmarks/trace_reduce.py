"""From a profiler trace to four things: the device's busy seconds in the
traced slice, the slice's length, the device operations that took most
time, and the longest idle gaps with what the harness was doing in them.

The reducer sorts nothing by kernel name (the program names none yet):
busy is the union of the intervals in which any operation ran on the
device, clipped to the slice the harness marked with a `bench:slice`
span on the same clock.  `load` keeps only what `reduce` reads, as plain
lists, so a trimmed trace is a JSON file (tests/fixtures).
"""

from __future__ import annotations

import glob
import os
import shutil
import tempfile
import time
from contextlib import contextmanager

SLICE = "bench:slice"
DEVICE_PLANE = "/device:TPU:"
# lines of a device plane that are not operations: groupings of the ops
# on the op line, drawn over the same time
NOT_OPS = ("Steps", "XLA Modules", "XLA TraceMe", "Framework Name Scope",
           "Source code")
OP_LINE = "XLA Ops"
# the device's trace buffer overflowed: later events are missing, so busy
# time reads low (seen at ~6.3 M events, 4 s of the write cell's crc loop);
# the readers then give nothing
DROPPED = "Trace Buffers Dropped"


def short(name: str) -> str:
    """The trace names an op by its whole HLO line; keep the result's
    name, the opcode and what the op calls: `%fusion.26 fusion
    %fused_computation.6.clone.clone`."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name[:120]
    depth, i = 0, 0
    for i, ch in enumerate(rest):      # skip the result's shape
        depth += ch in "([{"
        depth -= ch in ")]}"
        if ch == " " and depth == 0:
            break
    opcode = rest[i + 1:].split("(", 1)[0]
    tail = ""
    for key in ("custom_call_target=", "calls=", "body="):
        if key in rest:
            tail = " " + rest.split(key, 1)[1].split(",", 1)[0].strip('"')
            break
    return f"{head} {opcode}{tail}"[:120]


def load(path: str) -> dict:
    """An .xplane.pb -> {"planes": [{"name", "lines": [{"name",
    "events": [[name, start_ns, duration_ns], ...]}]}]}: device planes
    whole, host planes only for the harness's own `bench:` spans."""
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        dev = plane.name.startswith(DEVICE_PLANE)
        lines = []
        for line in plane.lines:
            evs = [[e.name, float(e.start_ns), float(e.duration_ns)]
                   for e in line.events
                   if dev or e.name.startswith("bench:")]
            if evs:
                lines.append({"name": line.name, "events": evs})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def _union(iv: list) -> list:
    """Sorted, merged [start, end] intervals."""
    out: list = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def reduce(trace: dict, top: int = 10) -> dict:
    """-> {"busy_s", "window_s", "chips", "device_ops": [[name, s]],
    "idle_gaps": [[label, s]]}; busy_s is the mean over the device
    planes.  Raises where the trace holds no slice span."""
    spans = []   # the harness's spans, host planes
    devs = []    # per device plane: op events
    dropped = False
    for plane in trace["planes"]:
        if plane["name"].startswith(DEVICE_PLANE):
            lines = plane["lines"]
            dropped |= any(e[0] == DROPPED for ln in lines
                           for e in ln["events"] if ln["name"] != OP_LINE)
            ops = [ln for ln in lines if ln["name"] == OP_LINE] or [
                ln for ln in lines if ln["name"] not in NOT_OPS]
            devs.append([e for ln in ops for e in ln["events"]])
        else:
            spans += [e for ln in plane["lines"] for e in ln["events"]]
    cut = [e for e in spans if e[0] == SLICE]
    if not cut:
        raise ValueError("trace holds no bench:slice span")
    w0, w1 = cut[0][1], cut[0][1] + cut[0][2]
    busy, by_name, gaps = [], {}, []
    for evs in devs:
        iv = []
        for name, s, d in evs:
            s, e = max(s, w0), min(s + d, w1)
            if e > s:
                iv.append([s, e])
                name = short(name)
                by_name[name] = by_name.get(name, 0.0) + (e - s)
        merged = _union(iv)
        busy.append(sum(e - s for s, e in merged))
        edges = [w0] + [t for pair in merged for t in pair] + [w1]
        gaps += [(edges[i + 1] - edges[i], edges[i], edges[i + 1])
                 for i in range(0, len(edges), 2) if edges[i + 1] > edges[i]]
    labelled: dict = {}
    for d, g0, g1 in sorted(gaps, reverse=True)[:100]:
        open_ = sorted({n for n, s, dd in spans
                        if n != SLICE and s < g1 and s + dd > g0})
        label = "+".join(open_) or "unattributed"
        labelled[label] = labelled.get(label, 0.0) + d
    n = max(len(devs), 1)
    return {
        "busy_s": sum(busy) / n / 1e9,
        "window_s": (w1 - w0) / 1e9,
        "chips": len(devs),
        "events": sum(len(evs) for evs in devs),
        "buffers_dropped": dropped,
        "device_ops": [[k, v / n / 1e9] for k, v in sorted(
            by_name.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[k, v / n / 1e9] for k, v in sorted(
            labelled.items(), key=lambda kv: -kv[1])[:top]],
    }


class Tracer:
    """`with tracer.slice(): ...` traces the body (host tracer at user
    spans only, Python tracer off) under a `bench:slice` span; `result()`
    reduces the trace in this process and deletes its directory."""

    def __init__(self) -> None:
        self.dir = ""
        self.stop_s = 0.0    # what the profiler took to write the trace

    @contextmanager
    def slice(self):
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation(SLICE):
                yield
        finally:
            t0 = time.monotonic()
            jax.profiler.stop_trace()
            self.stop_s = time.monotonic() - t0

    def result(self) -> dict:
        try:
            found = glob.glob(os.path.join(
                self.dir, "plugins", "profile", "*", "*.xplane.pb"))
            if not found:
                raise RuntimeError("the profiler wrote no trace")
            t0 = time.monotonic()
            out = reduce(load(found[0]))
            out["trace_bytes"] = os.path.getsize(found[0])
            out["stop_trace_s"] = self.stop_s
            out["reduce_s"] = time.monotonic() - t0
            return out
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def main(argv) -> int:
    """`python benchmarks/trace_reduce.py <file.xplane.pb>`: every plane
    and line with its event count and first names, then the reduction:
    for looking at one trace by hand."""
    import json

    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(argv[1]).planes:
        print("plane", plane.name)
        for line in plane.lines:
            evs = list(line.events)
            names = sorted({e.name for e in evs})
            print(f"  line {line.name!r}: {len(evs)} events, "
                  f"{len(names)} names, e.g. {names[:6]}")
    print(json.dumps(reduce(load(argv[1])), indent=1))
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main(sys.argv))
