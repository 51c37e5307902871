"""Unit + property tests for the overlap heuristics (paper Defs. 7-11)."""
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ModuleNotFoundError:  # optional dep: degrade to seeded sampling
    from repro.testing.hypothesis_fallback import given, settings
    from repro.testing.hypothesis_fallback import strategies as st

from repro.core import overlap as ovl

finite_radii = st.floats(0.05, 50.0, allow_nan=False, allow_infinity=False)
finite_d = st.floats(0.0, 120.0, allow_nan=False, allow_infinity=False)


def test_ball_volume_known_values():
    # V(n=2, r=1) = pi; V(n=3, r=1) = 4/3 pi; V(n=3, r=2) = 32/3 pi
    assert np.isclose(np.exp(ovl.ball_log_volume(2, jnp.float32(1.0))), np.pi, rtol=1e-5)
    assert np.isclose(np.exp(ovl.ball_log_volume(3, jnp.float32(1.0))), 4 / 3 * np.pi, rtol=1e-5)
    assert np.isclose(np.exp(ovl.ball_log_volume(3, jnp.float32(2.0))), 32 / 3 * np.pi, rtol=1e-5)


def test_cap_half_ball():
    # theta = pi/2 (cos = 0): cap is exactly half the ball.
    for n in (2, 3, 7, 20):
        v = np.exp(ovl.cap_log_volume(n, jnp.float32(1.0), jnp.float32(0.0)))
        half = 0.5 * np.exp(ovl.ball_log_volume(n, jnp.float32(1.0)))
        assert np.isclose(v, half, rtol=1e-4), n


@pytest.mark.parametrize("n_dim", [2, 3, 5])
def test_lens_volume_monte_carlo(n_dim):
    # own deterministic stream: the shared fixture's state depends on test
    # ordering, and in 5 dims the lens is a tiny fraction of the box
    rng = np.random.default_rng(42 + n_dim)
    r1, r2, d = 1.0, 0.8, 1.1
    lo, hi = -1.2, 2.0
    pts = rng.uniform(lo, hi, size=(800_000, n_dim))
    in1 = (pts**2).sum(1) <= r1**2
    c2 = np.zeros(n_dim)
    c2[0] = d
    in2 = ((pts - c2) ** 2).sum(1) <= r2**2
    mc = (in1 & in2).mean() * (hi - lo) ** n_dim
    closed = float(
        jnp.exp(ovl.intersection_log_volume(n_dim, jnp.float32(r1), jnp.float32(r2), jnp.float32(d)))
    )
    assert np.isclose(mc, closed, rtol=0.08), (mc, closed)


@pytest.mark.parametrize(
    "n_dim, lens_share",
    # I_{sin^2 theta}((n+1)/2, 1/2) / 2 at cos theta = 1/4 (scipy.special.betainc)
    [(5, 0.27520752), (20, 0.12497295), (128, 0.0019894313)],
)
def test_vbm_vanishes_at_128d_where_dbm_does_not(n_dim, lens_share):
    """Two equal balls at centre distance d = r / 2.  VBM's rate is each
    cap's share of its ball, which falls with the dimension: at n = 128 it
    is 0.002, far below xi_min = 0.4, so the pair takes the decision's
    "low" branch.  DBM's (2r - d) / d, clipped at 1, does not depend on n:
    above xi_max = 0.8, the pair merges."""
    r, d = jnp.float32(1.0), jnp.float32(0.5)
    vbm = float(ovl.vbm_rate(r, r, d, n_dim))
    assert vbm == pytest.approx(lens_share, rel=1e-4)
    assert float(ovl.dbm_rate(r, r, d)) == 1.0
    if n_dim == 128:
        assert vbm < 0.01 * 0.4


def test_dbm_partial_closed_form():
    # partial case: h1 + h2 == r1 + r2 - d  =>  D = (r1 + r2 - d) / d
    r1, r2, d = 2.0, 1.5, 3.0
    got = float(ovl.dbm_rate(jnp.float32(r1), jnp.float32(r2), jnp.float32(d)))
    assert np.isclose(got, (r1 + r2 - d) / d, rtol=1e-5)


@settings(max_examples=200, deadline=None)
@given(r1=finite_radii, r2=finite_radii, d=finite_d)
def test_rates_bounded_and_cases(r1, r2, d):
    """Property (Defs. 7/10): rates live in [0,1]; degenerate cases exact."""
    for fn in (lambda: ovl.vbm_rate(jnp.float32(r1), jnp.float32(r2), jnp.float32(d), 8),
               lambda: ovl.dbm_rate(jnp.float32(r1), jnp.float32(r2), jnp.float32(d))):
        rate = float(fn())
        assert 0.0 <= rate <= 1.0 + 1e-6
        if d >= r1 + r2:
            assert rate == 0.0
        elif d <= abs(r1 - r2):
            assert rate == 1.0


@settings(max_examples=100, deadline=None)
@given(r1=finite_radii, r2=finite_radii, d=finite_d)
def test_vbm_symmetry(r1, r2, d):
    a = float(ovl.vbm_rate(jnp.float32(r1), jnp.float32(r2), jnp.float32(d), 6))
    b = float(ovl.vbm_rate(jnp.float32(r2), jnp.float32(r1), jnp.float32(d), 6))
    assert np.isclose(a, b, atol=1e-5)


def test_vbm_monotone_in_distance():
    """Pulling two fixed balls apart can only shrink the volume rate."""
    r1 = jnp.float32(1.0)
    r2 = jnp.float32(0.7)
    ds = jnp.linspace(0.0, 2.0, 50)
    rates = np.array([float(ovl.vbm_rate(r1, r2, d, 8)) for d in ds])
    assert np.all(np.diff(rates) <= 1e-5)


def test_obm_rate_counts():
    got = float(ovl.obm_rate(jnp.float32(6), jnp.float32(10), jnp.float32(14),
                             jnp.float32(1.0), jnp.float32(1.0), jnp.float32(1.5)))
    assert np.isclose(got, 6 / 24)


def test_overlap_matrix_methods(blob_data):
    x = blob_data[:500]
    pivots = jnp.asarray(np.stack([x[:250].mean(0), x[250:].mean(0)]))
    radii = jnp.asarray(
        np.array(
            [np.linalg.norm(x[:250] - np.asarray(pivots)[0], axis=1).max(),
             np.linalg.norm(x[250:] - np.asarray(pivots)[1], axis=1).max()],
            np.float32,
        )
    )
    assign = jnp.asarray(np.repeat([0, 1], 250).astype(np.int32))
    for method in ("vbm", "dbm", "obm"):
        m = ovl.overlap_matrix(method, pivots, radii, x=jnp.asarray(x), assign=assign)
        m = np.asarray(m)
        assert m.shape == (2, 2)
        assert np.allclose(np.diag(m), 0.0)
        assert np.allclose(m, m.T, atol=1e-5)
        assert (m >= 0).all() and (m <= 1 + 1e-6).all()
