"""The least work each cell's algorithm needs, reckoned from the
configuration's shapes and counts of completed work: never from a
kernel's name, tile or implementation.  Both are bound by HBM bytes
(`bound: hbm`): GF(2^8) encode is a few XORs a byte and the
CRUSH sweep has no published integer peak to stand on (PERF.md).
"""

from __future__ import annotations

import json
import os

BOUND = "hbm"
PEAK_KEY = "hbm_bytes_per_s"


def ec_write_bytes(cfg: dict, done: dict) -> float:
    """An object of S bytes is read once and m/k of it written as coding
    shards (the crc rides the same pass): S * (1 + m/k)."""
    return done["objects"] * done["object_bytes"] * (1 + cfg["m"] / cfg["k"])


def crush_bytes(cfg: dict, done: dict) -> float:
    """4 B of id in and 4 B a replica out for every id placed."""
    return done["ids"] * 4 * (1 + cfg["num_rep"])


def peak(device_kind: str, key: str = PEAK_KEY) -> float:
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(f"no peaks for device kind {device_kind!r}: add it "
                       "to benchmarks/peaks.json with its source")
    return float(table[device_kind][key])


def roofline_pct(nbytes: float, device_kind: str, busy_s: float) -> float:
    """100 * (least seconds at the HBM peak) / (seconds the device was
    busy)."""
    return 100.0 * (nbytes / peak(device_kind)) / busy_s
