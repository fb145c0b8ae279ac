"""Traffic kind `rados_closed_loop_clay`: `rados_closed_loop`'s write
loop against a pool whose code is clay (`plugin=clay k=8 m=4 d=11`:
every 32 KiB stripe a codeword of its own, 64 sub-chunks of 64 B a 4 KiB
chunk, pairwise coupling around a per-layer MDS code).

Set-up, warm-up, the window, its counters and the traced slice are
`rados_closed_loop`'s, inherited: the same client calls, queue, crc and
store as the isa cell.  What names the code is replaced:

- `check` holds the stored shards to `reference_clay.clay_shards`, and
  adds `repair_wrong`: for `check_repairs_of` of the sampled objects and
  one shard of each, drawn by the seed, the program rebuilds the shard
  through the queue's own repair from the repair sub-chunks of the d
  other shards as the OSDs' stores hold them (16 of the 64 of every
  stripe: 32 x `[11, 16, 64]` an object), and the result has to equal
  the stored shard and the reference's.  After the close, untimed.
- `control`: the reference store coding with the scalar MDS code alone,
  no coupling: every read-back still succeeds, the four coding shards of
  every object are other bytes, and the repair property is gone.

What this driver takes from the program beside `rados_closed_loop`'s
list (`benchmarks/README.md`): `ceph_tpu.ec.codec_from_profile` (a codec
made from the configuration's profile through `ec/registry.py`), the
codec's `repair_layers(lost)`, `get_sub_chunk_count()` and `d`,
`StripeBatchQueue.clay_repair(codec, lost, helpers, planes, chunk=)` (a
helper's row is its repair sub-chunks stripe after stripe, `chunk` the
bytes of one codeword's chunk: the configuration's `stripe_unit`), and
the codec module's counter `clay.dev_calls()` (exported as
`clay.dev_calls`; left out where the program has none).

A program whose queue takes no `chunk` codes a shard as ONE codeword
(sub-chunks of 2,048 B a 1 MiB object: this repo before PR 37).  That is
another stored format than the configuration's, so `setup` ends the run
at once there: such a program cannot run this cell.
"""

from __future__ import annotations

import inspect

import numpy as np

import reference
import reference_clay
from drivers import rados_closed_loop
from drivers.rados_closed_loop import payload


class Driver(rados_closed_loop.Driver):
    codec = None   # made from the configuration's profile at the first repair
    q = None       # `close` reads it, also after a `setup` that raised

    def setup(self) -> None:
        if self.sys is None:
            from ceph_tpu.tpu.queue import StripeBatchQueue

            if "chunk" not in inspect.signature(
                    StripeBatchQueue.clay_repair).parameters:
                raise RuntimeError(
                    "this program's clay pool codes a shard as one "
                    "codeword, not each stripe by itself: it cannot hold "
                    f"{self.cfg['name']}'s stored format")
        super().setup()

    def counters(self) -> dict:
        out = super().counters()
        if self.q is not None:
            from ceph_tpu.ec import clay

            # the check lays these files over the parent's checkout too,
            # whose clay has no counter: the metric is then left out
            calls = getattr(clay, "dev_calls", None)
            if calls is not None:
                out["clay.dev_calls"] = calls()
        return out

    def _repair(self, oid: str, lost: int, held: dict) -> bytes:
        """Shard `lost` of `oid` rebuilt from the repair layers of the
        other shards' stored bytes (`held`), by the program's queue."""
        if self.q is None:   # the reference store in the program's place
            return self.sys.repair(oid, lost)
        if self.codec is None:
            from ceph_tpu.ec import codec_from_profile

            self.codec = codec_from_profile(self.cfg["ec_profile"])
        codec, unit = self.codec, self.cfg["stripe_unit"]
        subs = codec.get_sub_chunk_count()
        layers = codec.repair_layers(lost)
        helpers = sorted(s for s in held if s != lost)[:codec.d]
        # of every stripe of a helper's shard, its repair sub-chunks
        planes = np.stack([
            np.frombuffer(held[h][0], dtype=np.uint8).reshape(
                -1, subs, unit // subs)[:, layers].reshape(-1)
            for h in helpers])
        return np.asarray(self.q.clay_repair(
            codec, lost, helpers, planes, chunk=unit)).tobytes()

    def _readback(self, i: int) -> None:
        try:
            same = self.sys.read(f"obj_{i}") == payload(
                self.seed, i, self.t["object_bytes"])
        except Exception:  # noqa: BLE001 — an acknowledged write is lost
            same = False
        if not same:
            with self._lock:
                self.wrong += 1

    def check(self) -> dict:
        """`rados_closed_loop`'s comparisons with the clay reference in
        Reed-Solomon's place, and `repair_wrong`."""
        cfg, t = self.cfg, self.t
        done = [i for i, _t0, _t1, ok in self.ops if ok]
        self._fan(self._readback, done)
        rng = np.random.default_rng([self.seed, 4])
        some = [int(i) for i in rng.choice(
            done, size=min(t["check_shards_of"], len(done)),
            replace=False)] if done else []
        # one shard of each of the first `check_repairs_of` of them
        lose = dict(zip(some, np.random.default_rng([self.seed, 5]).integers(
            cfg["k"] + cfg["m"], size=t["check_repairs_of"])))
        want, got_crcs, missing, bad, repair_bad = [], [], 0, 0, 0
        for i in some:
            held = self.sys.stored(f"obj_{i}")
            missing += cfg["k"] + cfg["m"] - len(held)
            sh = reference_clay.clay_shards(
                payload(self.seed, i, t["object_bytes"]), cfg)
            for s, (data, crc) in held.items():
                bad += data != sh[s].tobytes()
                want.append(sh[s])
                got_crcs.append(crc)
            if i in lose:
                lost = int(lose[i])
                try:
                    got = self._repair(f"obj_{i}", lost, held)
                except Exception:  # noqa: BLE001 — a failed repair is wrong
                    got = None
                repair_bad += not (lost in held and got == held[lost][0]
                                   and got == sh[lost].tobytes())
        crcs = reference.crc32c_rows(np.stack(want)) if want else []
        return {"ops_failed": [len(self.ops) - len(done), 0],
                "no_op_compared": [int(not done), 0],
                "readback_wrong": [self.wrong, 0],
                "shards_missing": [missing, 0],
                "shards_wrong": [int(bad), 0],
                "crcs_wrong": [int(sum(int(a) != b for a, b in
                                       zip(crcs, got_crcs))), 0],
                "repair_wrong": [int(repair_bad), 0]}


def control(cfg: dict, traffic: dict, seed: int) -> Driver:
    """The reference store in the program's place with one guarantee
    broken: the shards are the scalar MDS code's, the coupling left out."""
    return Driver(cfg, traffic, seed,
                  system=reference_clay.RefStore(cfg, coupled=False))
