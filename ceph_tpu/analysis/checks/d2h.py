"""no-d2h-on-hot-path: the device-resident payload contract, enforced.

PR 6's staging pipeline keeps payloads device-resident from messenger
receive through encode/crc to store apply, with only metadata crossing
back to host.  The contract dies by a thousand cuts: one convenient
``np.asarray(...)`` / ``bytes(...)`` on a device buffer inside the
messenger fast-dispatch path or the StripeBatchQueue worker quietly
reintroduces the per-op device round trip the whole refactor removed
(a kernel that is fast alone and moves nothing end to end).

Since PR 18 this is the (loop ∪ device_worker, may-d2h) cell of the
shared thread-role engine: roots (every ``async def``, fast-dispatch
``ms_dispatch``, loop-scheduled callbacks, ``StripeBatchQueue._worker``
and future callbacks that resolve on it) come from
``analysis/threadmodel.py``; this module owns only the host-
materialization primitives: ``np.asarray`` / ``np.array`` /
``jnp.asarray``, ``.tolist()``, ``.tobytes()``, and ``bytes(...)``
applied to a value.

Accepted legacy debt lives in the baseline like any other check —
EXCEPT in the new pipeline modules themselves (``tpu/staging.py``,
``ops/crc32c_device.py``): violations there are never baselineable
(``--write-baseline`` refuses to record them), so the pipeline's own
code hard-errors the build.  Sanctioned fetches (the engine's own
batched d2h, 4-byte metadata digests) annotate the line with
``# cephlint: disable=no-d2h-on-hot-path — why``.
"""

from __future__ import annotations

import ast
from typing import List, Tuple

from ceph_tpu.analysis.checks.blocking import NoBlockingOnLoop
from ceph_tpu.analysis.framework import NEVER_BASELINE_PREFIXES, call_name
from ceph_tpu.analysis.threadmodel import (
    ROLE_DEVICE, ROLE_LOOP, FuncInfo, body_walk,
)

# host-materialization call names (module-qualified numpy/jax spellings
# the repo actually uses)
_MATERIALIZERS = {"np.asarray", "np.array", "numpy.asarray",
                  "numpy.array", "jnp.asarray", "jnp.array"}
_MATERIALIZER_METHODS = {"tolist", "tobytes"}

# the new pipeline modules hard-error: debt here is never accepted
_HARD_PATHS = ("ceph_tpu/tpu/staging.py", "ceph_tpu/ops/crc32c_device.py")


class NoD2HOnHotPath(NoBlockingOnLoop):
    name = "no-d2h-on-hot-path"
    description = ("host materialization of device buffers reachable "
                   "from the messenger fast-dispatch or "
                   "StripeBatchQueue._worker call graphs")
    scopes = ("ceph_tpu",)

    roles = (ROLE_LOOP, ROLE_DEVICE)

    def _message(self, prim: str, chain: List[str]) -> str:
        return (f"{prim} materializes a device buffer on host: "
                f"reachable via {' -> '.join(chain)} (device-resident "
                "payload contract: only metadata crosses to host on "
                "the hot path — annotate sanctioned metadata fetches "
                "with a disable + rationale)")

    # -- primitives: host materializations --------------------------------
    def _primitives(self, fn: FuncInfo) -> List[Tuple[int, str]]:
        out: List[Tuple[int, str]] = []
        for node in body_walk(fn.node):
            if not isinstance(node, ast.Call):
                continue
            cn = call_name(node)
            base = cn.split(".")[-1]
            if cn in _MATERIALIZERS:
                out.append((node.lineno, f"{cn}()"))
            elif cn == "bytes" and node.args:
                out.append((node.lineno, "bytes()"))
            elif "." in cn and base in _MATERIALIZER_METHODS:
                out.append((node.lineno, f"{cn}()"))
        return out


# register the hard-error scope with the baseline writer: pipeline-
# module debt for this check can never be accepted silently
for _p in _HARD_PATHS:
    NEVER_BASELINE_PREFIXES.append((NoD2HOnHotPath.name, _p))
