"""The benchmark's fluid capacity (bench/fluid.py), from which every cell's
arrival rate follows, against the program's own
(`repro.core.locality.capacity_hot_rack`) on the rack model.

    python -m pytest -q bench/tests/test_fluid.py
"""

from __future__ import annotations

import pytest

import small  # noqa: F401  (puts bench/ and src/ on the path)
from fluid import capacity


@pytest.mark.parametrize("m,p_hot,expected", [
    (24, 0.5, 10.0), (10008, 0.0, 5004.0), (10008, 0.5, 3338.0),
    (24, 1.0, 7.5), (240, 0.5, 82.0), (4008, 0.9, None)])
def test_capacity_matches_the_program(m, p_hot, expected):
    from repro.core import locality as loc
    rates = (0.5, 0.45, 0.25)
    ours = capacity(m, 6, rates, p_hot)
    assert ours == loc.capacity_hot_rack(loc.Topology(m, 6), loc.Rates(*rates),
                                         p_hot)
    if expected is not None:
        assert ours == expected
