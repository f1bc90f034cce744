"""The copied generators give what the program's own give."""

import numpy as np
import pytest

from harness import swarm


@pytest.mark.parametrize("homogeneous", [True, False])
def test_rpg_positions_and_rates(homogeneous):
    from repro.core import RadioParams, RPGMobility, RPGParams, rate_matrix
    mob = RPGMobility(RPGParams(n_uavs=40, area_m=300.0,
                                homogeneous=homogeneous), seed=7)
    want = mob.positions(5, seed=7)
    got = swarm.rpg_positions(40, 300.0, 5, 7, homogeneous=homogeneous)
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(swarm.rate_matrix(got[3]),
                                  rate_matrix(want[3], RadioParams()))


def test_hotspot_sources_match_the_snapshot_draw():
    rng = np.random.default_rng(5)
    want = rng.integers(0, 3, 50).astype(np.int64)
    np.testing.assert_array_equal(swarm.hotspot_sources(50, 3, 16, 5), want)


def test_frames_are_seeded():
    a = swarm.frames(2, (4, 5, 3), 2**31 + 9)
    assert a.dtype == np.float32 and a.shape == (2, 4, 5, 3)
    np.testing.assert_array_equal(a, swarm.frames(2, (4, 5, 3), 2**31 + 9))
    assert not np.array_equal(a, swarm.frames(2, (4, 5, 3), 9))
