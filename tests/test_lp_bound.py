"""The scheduler's in-graph LP optimum (Eq. 3) and the balance gap it
gives, against the host enumeration ``max_induced_density``."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.placement import max_induced_density
from repro.engine import MicroEPEngine


def _input(rng, e, g, skew):
    loads = rng.zipf(skew, size=e).clip(max=50) * rng.integers(1, 40, e)
    return np.stack([rng.multinomial(int(n), np.ones(g) / g)
                     for n in loads]).astype(np.int32)


@pytest.mark.parametrize("grid,experts,strategy,profiles,seed", [
    ((2, 2), 8, "latin", None, 0),
    ((2, 4), 16, "latin", None, 1),
    ((3, 4), 24, "random", None, 2),
    ((2, 2), 8, "vanilla", None, 3),
    ((2, 4), 16, "latin", "2,2,1,1,3,1,1,1", 4),
    ((3, 2), 12, "random", "1,2,1,3,1,1", 5),
])
def test_lp_bound_is_eq3(grid, experts, strategy, profiles, seed):
    eng = MicroEPEngine.build(experts, grid, placement=strategy,
                              device_profiles=profiles)
    rng = np.random.default_rng(seed)
    g = grid[0] * grid[1]
    weights = None if profiles is None else \
        np.asarray(profiles.split(","), np.float64)
    for skew in (1.3, 2.0, 3.0):
        input_eg = _input(rng, experts, g, skew)
        out = eng.scheduler(jnp.asarray(input_eg))
        want = max_induced_density(eng.placement, input_eg.sum(axis=1),
                                   num_samples=0, weights=weights)
        # the engine mean-normalizes weights; the optimum scales with them
        if weights is not None:
            want *= weights.mean()
        assert float(out.lp_bound) == pytest.approx(want, rel=1e-5)
        assert float(out.balance_gap) >= 1.0 - 1e-6


def test_one_device_gap_is_exactly_one():
    eng = MicroEPEngine.build(8, (1, 1), placement="vanilla")
    input_eg = np.array([[5], [0], [17], [3], [1], [9], [2], [40]],
                        np.int32)
    out = eng.scheduler(jnp.asarray(input_eg))
    assert float(out.lp_bound) == pytest.approx(77.0)
    assert float(out.balance_gap) == 1.0


def test_no_bound_above_twelve_devices():
    eng = MicroEPEngine.build(16, (4, 4), placement="latin")
    out = eng.scheduler(jnp.ones((16, 16), jnp.int32))
    assert np.isnan(float(out.lp_bound))
    assert np.isfinite(float(out.balance))
