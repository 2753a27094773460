"""Unit + property tests for circulant operator algebra (paper Sec. 4)."""

import pytest

hypothesis = pytest.importorskip("hypothesis")  # optional dev dep; CI installs it
import hypothesis.strategies as st  # noqa: E402
import jax
import jax.numpy as jnp
import numpy as np

from repro.core.circulant import (
    Circulant,
    DenseOperator,
    compose_sensing_blur,
    densify,
    gaussian_circulant,
    moving_average_blur,
    partial_gaussian_circulant,
    random_omega,
    romberg_circulant,
)
from repro.ops.spectral import irfft

SETTINGS = dict(max_examples=25, deadline=None)


def _rand(key, *shape):
    return jax.random.normal(jax.random.PRNGKey(key), shape, jnp.float32)


# ---------------------------------------------------------------------------
# Representation & conventions
# ---------------------------------------------------------------------------


def test_first_row_convention_matches_paper():
    """Paper Sec. 4.2: A[i,j] = v[(j-i) mod n]."""
    row = jnp.asarray([1.0, 2.0, 3.0, 4.0, 5.0])
    C = Circulant.from_first_row(row)
    d = np.asarray(C.to_dense())
    n = 5
    v = np.asarray(row)
    for i in range(n):
        for j in range(n):
            assert d[i, j] == v[(j - i) % n]


def test_first_col_roundtrip():
    col = _rand(0, 9)
    C = Circulant.from_first_col(col)
    np.testing.assert_allclose(np.asarray(C.to_dense())[:, 0], col, rtol=1e-6)
    np.testing.assert_allclose(C.first_row, np.asarray(C.to_dense())[0], rtol=1e-6)


@hypothesis.given(n=st.integers(4, 257), seed=st.integers(0, 2**20))
@hypothesis.settings(**SETTINGS)
def test_matvec_matches_dense(n, seed):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    C = gaussian_circulant(k1, n)
    x = jax.random.normal(k2, (n,))
    dense = np.asarray(C.to_dense())
    scale = max(1.0, float(np.abs(dense @ np.asarray(x)).max()))
    np.testing.assert_allclose(
        np.asarray(C.matvec(x)), dense @ np.asarray(x), atol=2e-4 * scale
    )
    np.testing.assert_allclose(
        np.asarray(C.rmatvec(x)), dense.T @ np.asarray(x), atol=2e-4 * scale
    )


@hypothesis.given(n=st.integers(4, 128), seed=st.integers(0, 2**20))
@hypothesis.settings(**SETTINGS)
def test_gram_compose_inverse(n, seed):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    C = gaussian_circulant(keys[0], n)
    D = gaussian_circulant(keys[1], n)
    dc, dd = np.asarray(C.to_dense()), np.asarray(D.to_dense())
    atol = 1e-3 * max(1.0, float(np.abs(dc).max()) ** 2) * n
    np.testing.assert_allclose(np.asarray(C.gram().to_dense()), dc.T @ dc, atol=atol)
    np.testing.assert_allclose(
        np.asarray(C.compose(D).to_dense()), dc @ dd, atol=atol
    )
    # inverse of a well-conditioned shifted gram
    B = C.gram().add_scaled_identity(0.1, 1.0)
    np.testing.assert_allclose(
        np.asarray(B.inverse().to_dense()),
        np.linalg.inv(np.asarray(B.to_dense())),
        atol=1e-4,
    )


@pytest.mark.parametrize(
    "batch,n,nf",
    [
        ((), 16, 9),  # even n: DC and Nyquist bins
        ((), 17, 9),  # odd n: no Nyquist bin
        ((3,), 1024, 513),  # batched
        ((2, 3), 33, 17),  # two leading axes
        ((3,), 64, 40),  # longer than n//2 + 1: cropped
        ((3,), 63, 20),  # shorter than n//2 + 1: zero-padded
    ],
)
def test_irfft_matches_jnp(batch, n, nf):
    """The one-sided inverse in repro.ops.spectral is jnp.fft.irfft: both
    ignore the imaginary parts of the DC and Nyquist bins, and the
    circulant algebra built on it still matches its dense oracle."""
    k1, k2, k3, k4 = jax.random.split(jax.random.PRNGKey(n + nf), 4)
    X = jax.lax.complex(jax.random.normal(k1, batch + (nf,)),
                        jax.random.normal(k2, batch + (nf,)))
    assert float(jnp.min(jnp.abs(X[..., 0].imag))) > 0  # imaginary DC present
    got, want = irfft(X, n), jnp.fft.irfft(X, n=n, axis=-1)
    assert got.dtype == jnp.float32 and got.shape == batch + (n,)
    assert float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want)) <= 1e-6
    # the bins without a mirror count by their real parts alone
    real_dc = X.at[..., 0].set(X[..., 0].real)
    if n % 2 == 0 and nf > n // 2:
        real_dc = real_dc.at[..., n // 2].set(X[..., n // 2].real)
    np.testing.assert_allclose(np.asarray(irfft(real_dc, n)), np.asarray(got),
                               rtol=1e-5, atol=1e-6)

    C = gaussian_circulant(k3, n)
    col = np.asarray(C.col)
    dense = col[(np.arange(n)[:, None] - np.arange(n)[None, :]) % n]
    x = np.asarray(jax.random.normal(k4, batch + (n,)))
    scale = max(1.0, float(np.abs(x @ dense.T).max()))
    np.testing.assert_allclose(np.asarray(C.matvec(x)), x @ dense.T, atol=2e-4 * scale)
    np.testing.assert_allclose(np.asarray(C.rmatvec(x)), x @ dense, atol=2e-4 * scale)


def test_operator_norm_exact():
    C = gaussian_circulant(jax.random.PRNGKey(7), 64)
    np.testing.assert_allclose(
        float(C.operator_norm()),
        np.linalg.norm(np.asarray(C.to_dense()), 2),
        rtol=1e-5,
    )


def test_transpose_spectrum():
    C = gaussian_circulant(jax.random.PRNGKey(3), 33)
    np.testing.assert_allclose(
        np.asarray(C.transpose().to_dense()), np.asarray(C.to_dense()).T, atol=1e-4
    )


def test_batched_matvec():
    C = gaussian_circulant(jax.random.PRNGKey(1), 32)
    xb = _rand(2, 4, 3, 32)
    out = C.matvec(xb)
    assert out.shape == (4, 3, 32)
    np.testing.assert_allclose(
        np.asarray(out[1, 2]), np.asarray(C.matvec(xb[1, 2])), rtol=1e-5, atol=1e-5
    )


# ---------------------------------------------------------------------------
# Partial circulant A = P C (paper Sec. 4.3)
# ---------------------------------------------------------------------------


@hypothesis.given(
    n=st.integers(8, 120), frac=st.floats(0.2, 0.9), seed=st.integers(0, 2**20)
)
@hypothesis.settings(**SETTINGS)
def test_partial_matches_dense(n, frac, seed):
    m = max(1, int(n * frac))
    op = partial_gaussian_circulant(jax.random.PRNGKey(seed), n, m)
    assert op.shape == (m, n)
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (n,))
    ym = jax.random.normal(jax.random.PRNGKey(seed + 2), (m,))
    dense = np.asarray(op.to_dense())
    atol = 2e-4 * max(1.0, float(np.abs(dense).max())) * n
    np.testing.assert_allclose(np.asarray(op.matvec(x)), dense @ np.asarray(x), atol=atol)
    np.testing.assert_allclose(
        np.asarray(op.rmatvec(ym)), dense.T @ np.asarray(ym), atol=atol
    )


def test_project_back_scatter():
    op = partial_gaussian_circulant(jax.random.PRNGKey(0), 16, 5)
    y = jnp.arange(1.0, 6.0)
    full = op.project_back(y)
    assert full.shape == (16,)
    np.testing.assert_allclose(np.asarray(full[op.omega]), np.asarray(y))
    assert float(jnp.sum(jnp.abs(full))) == pytest.approx(float(jnp.sum(y)))


def test_omega_unique_sorted():
    om = random_omega(jax.random.PRNGKey(5), 100, 40)
    o = np.asarray(om)
    assert len(np.unique(o)) == 40
    assert (np.sort(o) == o).all()


def test_norm_bound_is_upper_bound():
    op = partial_gaussian_circulant(jax.random.PRNGKey(9), 96, 48)
    true = np.linalg.norm(np.asarray(op.to_dense()), 2)
    assert float(op.operator_norm_bound()) >= true - 1e-4


# ---------------------------------------------------------------------------
# Romberg random convolution (beyond-paper conditioning)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n", [16, 33, 128])
def test_romberg_is_orthogonal(n):
    C = romberg_circulant(jax.random.PRNGKey(11), n)
    d = np.asarray(C.to_dense())
    np.testing.assert_allclose(d.T @ d, np.eye(n), atol=1e-4)
    assert float(C.operator_norm()) == pytest.approx(1.0, abs=1e-5)


# ---------------------------------------------------------------------------
# Blur composition (paper Sec. 7)
# ---------------------------------------------------------------------------


def test_moving_average_blur_row():
    B = moving_average_blur(8, 3)
    d = np.asarray(B.to_dense())
    np.testing.assert_allclose(d[0], [1 / 3, 1 / 3, 1 / 3, 0, 0, 0, 0, 0], atol=1e-7)
    np.testing.assert_allclose(d.sum(axis=1), np.ones(8), atol=1e-6)  # row-stochastic


def test_blur_composition_is_product():
    key = jax.random.PRNGKey(2)
    C = gaussian_circulant(key, 32)
    B = moving_average_blur(32, 5)
    A = compose_sensing_blur(C, B)
    np.testing.assert_allclose(
        np.asarray(A.to_dense()),
        np.asarray(C.to_dense()) @ np.asarray(B.to_dense()),
        atol=1e-3,
    )


def test_compose_spectrum_is_exact_product():
    """Composition stores the pointwise product spectrum bit-exactly — no
    irfft->rfft round trip (what lets plan() shard composed spectra as-is)."""
    C = gaussian_circulant(jax.random.PRNGKey(2), 32)
    B = moving_average_blur(32, 5)
    np.testing.assert_array_equal(
        np.asarray(C.compose(B).spec), np.asarray(C.spec * B.spec)
    )


def test_moving_average_blur_validates_order():
    """order > n used to silently truncate (.at[:order].set clips) so the
    kernel no longer summed to 1; now it is a loud error."""
    with pytest.raises(ValueError, match="order"):
        moving_average_blur(8, 9)
    with pytest.raises(ValueError, match="order"):
        moving_average_blur(8, 0)
    with pytest.raises(ValueError, match="order"):
        moving_average_blur(8, -3)
    # order == n is the legal extreme: the full-window average
    B = moving_average_blur(8, 8)
    np.testing.assert_allclose(np.asarray(B.col), np.full(8, 1 / 8), atol=1e-7)
    for order in (1, 3, 8):
        s = float(moving_average_blur(8, order).col.sum())
        assert s == pytest.approx(1.0, abs=1e-6), order


def test_compose_rejects_size_mismatch():
    """n mismatch raises a shape error up front, not a cryptic spectral
    broadcast failure deep in the rfft algebra."""
    C = gaussian_circulant(jax.random.PRNGKey(0), 16)
    B = moving_average_blur(32, 3)
    with pytest.raises(ValueError, match="different sizes: n=16 vs n=32"):
        C.compose(B)
    with pytest.raises(ValueError, match="different signal lengths"):
        compose_sensing_blur(C, B)


# ---------------------------------------------------------------------------
# Memory-footprint claim (paper Fig. 3): O(n) vs O(n^2)
# ---------------------------------------------------------------------------


def test_footprint_linear_vs_quadratic():
    n = 1 << 10
    op = partial_gaussian_circulant(jax.random.PRNGKey(0), n, n // 2)
    circ_bytes = op.circ.col.nbytes + op.circ.spec.nbytes + op.omega.nbytes
    dense_bytes = densify(op).mat.nbytes
    # circulant rep must be >100x smaller at n=1024 and scale ~n vs ~n^2/2
    assert circ_bytes < dense_bytes / 100
    assert circ_bytes <= 16 * n + 64


def test_dense_operator_norm_bound_is_safe_upper_bound():
    op = DenseOperator(_rand(3, 20, 50))
    true = np.linalg.norm(np.asarray(op.mat), 2)
    bound = float(op.operator_norm_bound())
    assert true <= bound <= 4.0 * true  # valid and not absurdly loose
