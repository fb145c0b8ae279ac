#!/usr/bin/env python3
"""crushtool — build, test and inspect CRUSH maps.

Flag-compatible core of the reference tool (reference:
src/tools/crushtool.cc:112-218 for --build/--test and
src/crush/CrushTester.cc:472 for the placement-distribution test),
with the inversion this framework exists for: the --test sweep is ONE
staged device dispatch over the whole x-range (mapper.sweep_device)
instead of a scalar crush_do_rule loop.

Examples:
  crushtool.py --build --num_osds 64 host straw2 4 root straw2 0 -o map.bin
  crushtool.py -i map.bin --test --rule 0 --num-rep 3 --min-x 0 \\
      --max-x 9999 --show-statistics --show-utilization
  crushtool.py -i map.bin --reweight-item osd.5 3.638 -o map.bin
"""

from __future__ import annotations

import argparse
import json
import sys

import numpy as np

from ceph_tpu.core.encoding import Decoder, Encoder
from ceph_tpu.crush import map as cmap
from ceph_tpu.crush import mapper
from ceph_tpu.osd.map_codec import decode_crush, encode_crush

ITEM_NONE = cmap.ITEM_NONE


def build_map(num_osds: int, layers) -> cmap.CrushMap:
    """--build: bottom-up layers of (name, alg, size); size 0 = one
    bucket over everything below (reference crushtool.cc --build)."""
    m = cmap.CrushMap()
    alg_by_name = {"uniform": cmap.ALG_UNIFORM, "list": cmap.ALG_LIST,
                   "tree": cmap.ALG_TREE, "straw": cmap.ALG_STRAW,
                   "straw2": cmap.ALG_STRAW2}
    items = list(range(num_osds))
    weights = [0x10000] * num_osds
    type_id = 0
    for name, alg_name, size in layers:
        type_id += 1
        m.type_names[type_id] = name
        alg = alg_by_name[alg_name]
        if size == 0:
            groups = [items]
        else:
            groups = [items[i:i + size] for i in range(0, len(items), size)]
        new_items, new_weights = [], []
        at = 0
        for g in groups:
            w = weights[at:at + len(g)]
            bid = m.add_bucket(alg, type_id, g, w)
            new_items.append(bid)
            new_weights.append(sum(w))
            at += len(g)
        items, weights = new_items, new_weights
    return m


def place(flat, steps, num_rep: int, xs: np.ndarray, dev_w) -> np.ndarray:
    """The staged device sweep over xs (padded to whole chunks); where
    it overflows a capacity of its plan, the host sweep, whose fix-up
    batches are cut to what each chunk needs."""
    chunk = min(1 << 19, len(xs))
    padded = np.concatenate(
        [xs, np.full(-len(xs) % chunk, xs[-1], dtype=np.int32)])
    out, overflow = mapper.sweep_device(flat, steps, num_rep, padded, dev_w,
                                        chunk=chunk)
    if bool(overflow):
        return mapper.sweep(flat, steps, num_rep, xs, dev_w)
    return np.asarray(out)[:len(xs)]


def item_id(m: cmap.CrushMap, name: str) -> int:
    """`osd.N`, a bucket's name, or what -d calls a bucket without one
    (`bucket<N>` for id -N)."""
    if name.startswith("osd."):
        return int(name[4:])
    names = {f"bucket{-bid}": bid for bid in m.buckets}
    names.update({n: bid for bid, n in m.bucket_names.items()})
    if name not in names:
        raise SystemExit(f"crushtool: no item {name} in the map")
    return names[name]


def run_test(m: cmap.CrushMap, args) -> dict:
    rule_no = args.rule
    if rule_no >= len(m.rules):
        m.add_rule(cmap.Rule("test", [
            (cmap.OP_TAKE, min(m.buckets), 0),
            (cmap.OP_CHOOSELEAF_FIRSTN, args.num_rep, 1),
            (cmap.OP_EMIT, 0, 0)]))
        rule_no = len(m.rules) - 1
    rule = m.rules[rule_no]
    xs = np.arange(args.min_x, args.max_x + 1, dtype=np.int32)
    dev_w = np.full(m.max_devices, 0x10000, dtype=np.uint32)
    if args.weight:
        for osd, w in args.weight:
            dev_w[osd] = int(float(w) * 0x10000)
    out = place(m.flatten(), rule.steps, args.num_rep, xs, dev_w)

    valid = (out != ITEM_NONE) & (out >= 0)
    sizes = valid.sum(axis=1)
    stats = {
        "rule": rule_no,
        "num_rep": args.num_rep,
        "x_range": [args.min_x, args.max_x],
        "total_mappings": int(len(xs)),
        "bad_mappings": int((sizes < args.num_rep).sum()),
    }
    result = {"statistics": stats}
    if args.show_utilization or args.show_statistics:
        flat = out[valid]
        counts = np.bincount(flat, minlength=m.max_devices)
        expected = counts.sum() / max((dev_w > 0).sum(), 1)
        stats["device_utilization"] = {
            "min": int(counts.min()), "max": int(counts.max()),
            "mean": round(float(counts.mean()), 2),
            "stddev": round(float(counts.std()), 2),
            "expected_per_device": round(float(expected), 2),
        }
        if args.show_utilization:
            result["utilization"] = {
                f"osd.{i}": int(c) for i, c in enumerate(counts)}
    if args.show_mappings:
        result["mappings"] = {
            int(x): [int(o) for o in row if o != ITEM_NONE]
            for x, row in zip(xs[:args.max_show], out[:args.max_show])}
    return result


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="crushtool")
    p.add_argument("-i", "--infn", help="input map file")
    p.add_argument("-o", "--outfn", help="output map file")
    p.add_argument("-d", "--decompile", action="store_true",
                   help="decompile -i map to text (CrushCompiler role)")
    p.add_argument("-c", "--compile", dest="compilefn", metavar="TEXTFN",
                   help="compile a text map (write binary with -o)")
    p.add_argument("--build", action="store_true")
    p.add_argument("--num_osds", type=int, default=0)
    p.add_argument("layers", nargs="*",
                   help="--build layers: name alg size triples")
    p.add_argument("--reweight-item", nargs=2, action="append", default=[],
                   metavar=("NAME", "W"),
                   help="set an item's CRUSH weight (16.16 from the float "
                        "W) and carry it up through its ancestors")
    p.add_argument("--test", action="store_true")
    p.add_argument("--rule", type=int, default=0)
    p.add_argument("--num-rep", type=int, default=3)
    p.add_argument("--min-x", type=int, default=0)
    p.add_argument("--max-x", type=int, default=1023)
    p.add_argument("--show-statistics", action="store_true")
    p.add_argument("--show-utilization", action="store_true")
    p.add_argument("--show-mappings", action="store_true")
    p.add_argument("--max-show", type=int, default=32)
    p.add_argument("--weight", nargs=2, action="append", default=[],
                   type=str, metavar=("OSD", "W"))
    args = p.parse_args(argv)
    args.weight = [(int(o), w) for o, w in args.weight]

    if args.build:
        if args.num_osds <= 0 or len(args.layers) % 3:
            print("--build needs --num_osds and name alg size triples",
                  file=sys.stderr)
            return 1
        layers = [(args.layers[i], args.layers[i + 1],
                   int(args.layers[i + 2]))
                  for i in range(0, len(args.layers), 3)]
        m = build_map(args.num_osds, layers)
    elif args.compilefn:
        from ceph_tpu.crush.compiler import compile_text

        with open(args.compilefn) as f:
            m = compile_text(f.read())
    elif args.infn:
        with open(args.infn, "rb") as f:
            m = decode_crush(Decoder(f.read()))
    else:
        print("need --build, -c or -i", file=sys.stderr)
        return 1

    for name, w in args.reweight_item:
        if not m.adjust_item_weight(item_id(m, name),
                                    int(float(w) * 0x10000)):
            print(f"crushtool: no bucket holds {name}", file=sys.stderr)
            return 1

    if args.decompile:
        from ceph_tpu.crush.compiler import decompile

        text = decompile(m)
        if args.outfn:
            with open(args.outfn, "w") as f:
                f.write(text)
        else:
            sys.stdout.write(text)
        return 0

    if args.outfn:
        e = Encoder()
        encode_crush(e, m)
        with open(args.outfn, "wb") as f:
            f.write(e.bytes())
        print(f"wrote crush map to {args.outfn}")
    if args.test:
        print(json.dumps(run_test(m, args), indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
