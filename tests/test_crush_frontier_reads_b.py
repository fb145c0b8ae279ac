"""The firstn cases of crush_frontier_cases.py (which see): three
replicas by host on the second half of the maps."""

import pytest

import crush_frontier_cases as cases


@pytest.mark.parametrize("stage", cases.STAGES)
@pytest.mark.parametrize("name", cases.HALVES["b"])
def test_every_read_places_as_the_oracle(name, stage):
    cases.check_places_as_the_oracle(name, "firstn3", stage)
