#!/usr/bin/env python3
"""One cell of BENCHMARK.json, once, in one process that holds the chip:

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell is made of is found by name: BENCHMARK.json gives the
cell's configuration, traffic and metrics; `configs/<config>.json`,
`traffic/<traffic>.json` (which names its driver under `drivers/`) and
`metrics/<metric>.json` (which names its reader under `readers/`; a
metric split by cell, `<stem>.<suffix>`, is served by `<stem>.json`)
hold the rest.  The last line of stdout is the result; a run that finds no
TPU, or fewer chips than the cell asks, prints none and exits 2.
"""

from __future__ import annotations

import time

T_START = time.monotonic()   # set-up runs from here to the window's start

import argparse      # noqa: E402
import hashlib       # noqa: E402
import importlib     # noqa: E402
import json          # noqa: E402
import os            # noqa: E402
import platform      # noqa: E402
import subprocess    # noqa: E402
import sys           # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _p in (HERE, ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def note(kind: str, **kw) -> None:
    """A line for the reader of a log; the driver reads only the last."""
    print(json.dumps({"note": kind, **kw}), flush=True)


def _json(*parts: str) -> dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_cell(name: str) -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json; it has "
                         f"{sorted(cells)}")
    cell = cells[name]
    conf = {c["name"]: c for c in manifest["configs"]}[cell["config"]]
    with open(os.path.join(ROOT, conf["file"])) as f:
        cfg = json.load(f)

    end_to_end = [m for m in manifest["end_to_end"]
                  if name in m.get("workloads", [name])]
    # a per-layer metric that lists no cells is read in every cell that
    # reports the end-to-end metric it moves
    moved = {m["name"] for m in end_to_end}
    per_layer = [m for m in manifest["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in moved)]
    return {"cell": cell, "cfg": cfg,
            "traffic": _json("traffic", cell["traffic"] + ".json"),
            "end_to_end": end_to_end, "per_layer": per_layer}


def metric_how(name: str) -> dict:
    """`metrics/<name>.json`, or the file of the name's stem."""
    for stem in (name, name.split(".", 1)[0]):
        if os.path.exists(os.path.join(HERE, "metrics", stem + ".json")):
            return _json("metrics", stem + ".json")
    raise SystemExit(f"no metrics/{name}.json and none for its stem")


def device_block(chips: int, require_chip: bool) -> dict:
    import jax

    devs = jax.devices()
    block = {"platform": devs[0].platform, "kind": devs[0].device_kind,
             "count": len(devs)}
    if require_chip and (block["platform"] != "tpu" or len(devs) < chips):
        print(f"benchmarks/run.py: the cell needs {chips} TPU chip(s), jax "
              f"found {block}; refusing to run", file=sys.stderr)
        raise SystemExit(2)
    return block


def ensure_native() -> float:
    """Build csrc/ once per checkout and machine: a copied tree can carry
    objects built with another machine's -march=native."""
    t0 = time.monotonic()
    with open("/proc/cpuinfo") as f:
        flags = next((ln for ln in f if ln.startswith("flags")), "")
    stamp = hashlib.sha256((platform.node() + flags).encode()).hexdigest()
    path = os.path.join(HERE, ".native_built_on")
    built = all(os.path.exists(os.path.join(ROOT, "ceph_tpu", f))
                for f in ("libceph_tpu_native.so", "_fastec.so"))
    if built and os.path.exists(path) and open(path).read() == stamp:
        return 0.0
    csrc = os.path.join(ROOT, "csrc")
    subprocess.run(["make", "-C", csrc, "-s", "clean"], check=True,
                   stdout=subprocess.DEVNULL)
    subprocess.run(["make", "-C", csrc, "-s"], check=True,
                   stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
    with open(path, "w") as f:
        f.write(stamp)
    return time.monotonic() - t0


def memory_peak(chips: int) -> int:
    import jax

    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices()[:chips])


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             require_chip: bool = True, traffic_over: dict | None = None,
             control: bool = False) -> dict:
    """Set up, run the window, check; -> the result line as a dict.
    `require_chip=False` and `traffic_over` are for the tests, which
    rehearse the control flow on the CPU at a small size; `control` puts
    the driver's control in the program's place (control.py)."""
    spec = load_cell(workload)
    cfg, traffic = spec["cfg"], {**spec["traffic"], **(traffic_over or {})}
    # the compile cache lives in the checkout, at a fixed path, whatever
    # the environment says: the two sides of a comparison share nothing
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    device = device_block(spec["cell"]["chips"], require_chip)
    build_s = ensure_native()

    from ceph_tpu.tpu import devwatch, shapebucket

    shapebucket.setup_compile_cache()
    module = importlib.import_module("drivers." + traffic["driver"])
    driver = (module.control if control else module.Driver)(
        cfg, traffic, seed)
    try:
        driver.setup()
        dw = devwatch.watch().dump()["totals"]
        setup_s = time.monotonic() - T_START
        note("setup", setup_s=setup_s, native_build_s=build_s,
             phases=getattr(driver, "phases", {}),
             compile_s=dw["compile_seconds"], compiles=dw["compiles"],
             persist_hits=dw["cache_persist_hits"],
             persist_misses=dw["cache_persist_misses"],
             cache=shapebucket.compile_cache_dir())

        tracer = None
        if trace:
            from trace_reduce import Tracer

            tracer = Tracer()
        before = driver.counters()
        got = driver.window(seconds, tracer)
        after = driver.counters()
        device["memory_peak_bytes"] = memory_peak(spec["cell"]["chips"])
        note("window", **got["metrics"], **got.get("notes", {}),
             counters={k: after[k] - before.get(k, 0) for k in after})

        t0 = time.monotonic()
        compared = driver.check()
        note("check", seconds=time.monotonic() - t0)
    finally:
        driver.close()

    values = {**got["metrics"], "setup_s": setup_s}
    result = {"correct": all(v <= lim for v, lim in compared.values()),
              "attempted": got["attempted"], "failed": got["failed"]}
    if trace:
        reduced = tracer.result()
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        ctx = {"before": before, "after": after, "trace": reduced,
               "slice": got["slice"], "cfg": cfg, "traffic": traffic,
               "device_kind": device["kind"]}
        note("end_to_end_of_traced_run", **values)
        note("trace", **{k: v for k, v in reduced.items()
                         if k not in ("device_ops", "idle_gaps")},
             slice=got["slice"])
        values = {}
        for m in spec["per_layer"]:
            how = metric_how(m["name"])
            reader = importlib.import_module("readers." + how["kind"])
            v = reader.read(how.get("args", {}), ctx)
            if v is not None:
                values[m["name"]] = v
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
        shown = spec["per_layer"]
    else:
        shown = spec["end_to_end"]
    result["metrics"] = {m["name"]: {"value": values[m["name"]],
                                     "unit": m["unit"]}
                         for m in shown if m["name"] in values}
    result["device"] = device
    result["compared"] = {k: {"value": v, "limit": lim}
                          for k, (v, lim) in compared.items()}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    return report(run_cell(args.workload, args.seed, args.seconds,
                           bool(args.trace)))


def report(result: dict) -> int:
    """Each number compared beside its limit on stderr, then the result
    as the last line of stdout."""
    for name, c in result["compared"].items():
        print(f"compared {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
