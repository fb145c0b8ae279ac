"""The indep cases of crush_frontier_cases.py (which see): twelve shards
by host on the second half of the maps, where a map has twelve hosts."""

import pytest

import crush_frontier_cases as cases


@pytest.mark.parametrize("stage", cases.STAGES)
@pytest.mark.parametrize("name", [n for n in cases.HALVES["b"]
                                  if n != "legacy_alg"])
def test_every_read_places_as_the_oracle(name, stage):
    cases.check_places_as_the_oracle(name, "indep12", stage)
