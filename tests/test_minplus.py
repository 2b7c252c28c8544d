"""Property lockdown for the Pallas tropical (min-plus) matmul.

The kernel runs in interpret mode here (CPU CI path — the same code Mosaic
lowers on TPU); the oracle is the dense jnp broadcast in
``repro.kernels.ref``.  Deterministic grids cover the properties on every
run; the Hypothesis suite at the bottom fuzzes them further when
``hypothesis`` is installed (optional — without it the deterministic grid is
the coverage, not a skip of the whole module).
"""
from __future__ import annotations

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.kernels.minplus import minplus_matmul  # noqa: E402
from repro.kernels.ref import reference_minplus  # noqa: E402

INF = np.inf


def _mm(a, b):
    """Kernel under f64 (the solvers always call it inside ``enable_x64``)."""
    with jax.enable_x64(True):
        return minplus_matmul(jnp.asarray(a), jnp.asarray(b), interpret=True)


def _ref(a, b):
    with jax.enable_x64(True):
        return reference_minplus(jnp.asarray(a), jnp.asarray(b))


def _rand(rng, shape, p_inf=0.2):
    """Cost-like matrix: non-negative floats with +inf holes (infeasible
    hops), the only matrix population the solvers ever produce."""
    x = rng.uniform(0.0, 10.0, size=shape)
    x[rng.uniform(size=shape) < p_inf] = INF
    return x


def _check(a, b):
    val, idx = _mm(a, b)
    rval, ridx = _ref(a, b)
    np.testing.assert_array_equal(np.asarray(val), np.asarray(rval))
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(ridx))


# ------------------------------------------------------- deterministic grid
# deliberately off-tile shapes: the kernel pads to (8, 128) tiles internally
_SHAPES = [
    (1, 1, 1),
    (2, 3, 4),
    (8, 8, 8),
    (5, 128, 7),
    (9, 130, 3),     # crosses both the _BM=8 and _BK=128 tile boundaries
    (16, 16, 16),
]


@pytest.mark.parametrize("m,k,n", _SHAPES)
def test_matches_reference(m, k, n):
    rng = np.random.default_rng((m * 73856093 + k * 19349663 + n) % 2**32)
    _check(_rand(rng, (m, k)), _rand(rng, (k, n)))


@pytest.mark.parametrize("batch", [(1,), (3,), (2, 2)])
def test_batched_matches_reference(batch):
    rng = np.random.default_rng(7)
    _check(_rand(rng, batch + (4, 6)), _rand(rng, batch + (6, 5)))


def test_first_argmin_on_ties():
    # two equal minimizing k: the first index must win (np.argmin convention)
    a = np.array([[1.0, 1.0, 5.0]])
    b = np.array([[2.0], [2.0], [0.0]])
    val, idx = _mm(a, b)
    assert float(val[0, 0]) == 3.0
    assert int(idx[0, 0]) == 0


def test_inf_padding_absorbs():
    """Growing either operand with +inf rows/cols must not change the valid
    region — the exact property the solvers' shape padding relies on."""
    rng = np.random.default_rng(11)
    a, b = _rand(rng, (5, 6)), _rand(rng, (6, 4))
    val, idx = _mm(a, b)
    ap = np.pad(a, ((0, 3), (0, 10)), constant_values=INF)
    bp = np.pad(b, ((0, 10), (0, 5)), constant_values=INF)
    vp, ip = _mm(ap, bp)
    np.testing.assert_array_equal(np.asarray(vp)[:5, :4], np.asarray(val))
    np.testing.assert_array_equal(np.asarray(ip)[:5, :4], np.asarray(idx))


def test_all_inf_column_yields_index_zero():
    a = np.full((2, 3), INF)
    b = _rand(np.random.default_rng(3), (3, 2), p_inf=0.0)
    val, idx = _mm(a, b)
    assert np.all(np.isinf(np.asarray(val)))
    assert np.all(np.asarray(idx) == 0)  # jnp.argmin convention on all-inf


def test_associativity_of_values():
    """(A ∘ B) ∘ C == A ∘ (B ∘ C) on values — the tropical semiring law the
    multi-hop frontier composition depends on.  (Indices are relative to
    different factorizations, so only values are comparable.)"""
    rng = np.random.default_rng(23)
    a, b, c = _rand(rng, (4, 5)), _rand(rng, (5, 6)), _rand(rng, (6, 3))
    ab, _ = _mm(a, b)
    bc, _ = _mm(b, c)
    left, _ = _mm(np.asarray(ab), c)
    right, _ = _mm(a, np.asarray(bc))
    np.testing.assert_allclose(np.asarray(left), np.asarray(right),
                               rtol=1e-12, atol=0)


def test_shape_errors():
    with pytest.raises(ValueError, match="contraction"):
        _mm(np.zeros((2, 3)), np.zeros((4, 2)))
    with pytest.raises(ValueError, match="batch"):
        _mm(np.zeros((2, 2, 3)), np.zeros((3, 3, 2)))


def test_compiled_f64_raises():
    """Mosaic has no float64: a compiled (non-interpret) f64 call must say so
    rather than fall back to the interpreter or the jnp reference."""
    with jax.enable_x64(True):
        a = jnp.zeros((2, 3), jnp.float64)
        b = jnp.zeros((3, 2), jnp.float64)
        with pytest.raises(TypeError, match="float64"):
            minplus_matmul(a, b, interpret=False)


# ------------------------------------------------------ hypothesis fuzzing
try:
    from hypothesis import given, settings, strategies as st
    _HAS_HYPOTHESIS = True
except ImportError:  # optional dependency; deterministic grid still ran
    _HAS_HYPOTHESIS = False

if _HAS_HYPOTHESIS:

    @st.composite
    def _mats(draw):
        m = draw(st.integers(1, 12))
        k = draw(st.integers(1, 20))
        n = draw(st.integers(1, 12))
        seed = draw(st.integers(0, 2**16))
        p_inf = draw(st.sampled_from([0.0, 0.2, 0.9]))
        rng = np.random.default_rng(seed)
        return _rand(rng, (m, k), p_inf), _rand(rng, (k, n), p_inf)

    @settings(max_examples=25, deadline=None)
    @given(_mats())
    def test_hypothesis_matches_reference(ab):
        _check(*ab)

    @settings(max_examples=15, deadline=None)
    @given(_mats())
    def test_hypothesis_inf_padding_absorbs(ab):
        a, b = ab
        val, idx = _mm(a, b)
        ap = np.pad(a, ((0, 2), (0, 3)), constant_values=INF)
        bp = np.pad(b, ((0, 3), (0, 1)), constant_values=INF)
        vp, ip = _mm(ap, bp)
        m, n = a.shape[0], b.shape[1]
        np.testing.assert_array_equal(np.asarray(vp)[:m, :n],
                                      np.asarray(val))
        np.testing.assert_array_equal(np.asarray(ip)[:m, :n],
                                      np.asarray(idx))
else:

    @pytest.mark.skip(reason="hypothesis not installed; deterministic grid "
                             "above is the coverage")
    def test_hypothesis_suite_unavailable():
        pass
