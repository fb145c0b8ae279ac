"""The indep cases of crush_frontier_cases.py (which see): twelve shards
by host on the first half of the maps; and whether the frontier read
engages and falls back where it should."""

import jax
import pytest

import crush_frontier_cases as cases
from ceph_tpu.core import tracing
from ceph_tpu.core.tracing import COUNTS, NAME
from ceph_tpu.crush import mapper


@pytest.mark.parametrize("stage", cases.STAGES)
@pytest.mark.parametrize("name", cases.HALVES["a"])
def test_every_read_places_as_the_oracle(name, stage):
    cases.check_places_as_the_oracle(name, "indep12", stage)


def _gather_operands(jaxpr, out):
    """Shapes of the operands of every gather of a program, nested
    jaxprs included."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "gather":
            out.append(tuple(eqn.invars[0].aval.shape))
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                if hasattr(sub, "eqns") or hasattr(sub, "jaxpr"):
                    _gather_operands(getattr(sub, "jaxpr", sub), out)
    return out


@pytest.mark.parametrize("name,want", [("ec_rack", 0), ("wide_frontier", 1)])
def test_the_frontier_read_engages_and_falls_back(name, want):
    """On the benchmark's shape the budgeted program gathers from no
    bucket table (items, weights, sizes, types, w_idx, or what took
    their place) and the sweep's span counts no gathering level; with
    more hosts than _MAX_ONEHOT_FRONTIER the leaf level gathers, and
    the span says so."""
    flat, steps, numrep, w, xs, oracle = cases.case(name, "indep12")
    plan = mapper.sweep_plan(flat, steps, numrep, w)
    _, mid, _ = mapper._stage_programs(flat, steps, numrep, None, plan,
                                       False)
    # a bucket table is a constant of the program with a row a bucket
    # (the map has more buckets than the rule has slots, and fewer than
    # lanes or devices, so no other operand starts with that length)
    n_buckets = flat.items.shape[0]
    shapes = _gather_operands(jax.make_jaxpr(mid)(xs[:512], w).jaxpr, [])
    assert shapes and n_buckets not in (12, 512, len(w))
    from_tables = [s for s in shapes if s[0] == n_buckets]
    assert bool(from_tables) == bool(want), from_tables
    assert (mid.levels["gather"] > 0) == bool(want)
    n0 = len(tracing.recorder().held()[0])
    mapper.sweep_device(flat, steps, numrep, xs, w, chunk=cases.N_IDS // 2)
    span, = [r for r in tracing.recorder().held()[0][n0:]
             if r[NAME] == "crush.sweep"]
    counts = span[COUNTS]
    assert (counts["gather"] > 0) == bool(want)
    assert counts["const"] > 0 and (counts["onehot"] > 0) != bool(want)
