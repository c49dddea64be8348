"""Per-kernel allclose validation: Pallas (interpret=True on CPU) vs ref.py.

Per the assignment: sweep shapes/dtypes for each kernel and assert_allclose
against the pure-jnp oracle.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

jax.config.update("jax_enable_x64", False)


def _spd(key, n, dtype=jnp.float32):
    a = jax.random.normal(key, (n, n), jnp.float32)
    k = a @ a.T / n + 2.0 * jnp.eye(n)
    return k.astype(dtype)


TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


# ---------------------------------------------------------------------------
# Matérn covariance build
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n,m,d", [(128, 128, 128), (256, 128, 128),
                                   (128, 384, 256), (100, 77, 5),
                                   (1, 1, 1), (130, 257, 20)])
@pytest.mark.parametrize("dtype", [jnp.float32])
def test_matern_gram_matches_ref(n, m, d, dtype):
    kx, ky = jax.random.split(jax.random.PRNGKey(n * 1000 + m))
    x = jax.random.uniform(kx, (n, d), dtype, minval=-3, maxval=3)
    y = jax.random.uniform(ky, (m, d), dtype, minval=-3, maxval=3)
    got = ops.matern52_gram(x, y, 1.3, 0.7, implementation="pallas")
    want = ref.matern52_gram_ref(x, y, 1.3, 0.7)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=TOL[dtype])


def test_matern_gram_bf16():
    kx = jax.random.PRNGKey(7)
    x = jax.random.uniform(kx, (128, 128), jnp.bfloat16, minval=-2, maxval=2)
    got = ops.matern52_gram(x, x, 1.0, 1.0, implementation="pallas")
    want = ref.matern52_gram_ref(x.astype(jnp.float32),
                                 x.astype(jnp.float32), 1.0, 1.0)
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want),
                               rtol=2e-2, atol=2e-2)


# ---------------------------------------------------------------------------
# Blocked triangular solve (the paper's O(n^2) append hot path)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n", [128, 256, 512, 200, 1000])
@pytest.mark.parametrize("trans", [False, True])
def test_trsv_vector_matches_ref(n, trans):
    key = jax.random.PRNGKey(n + int(trans))
    l = jnp.linalg.cholesky(_spd(key, n))
    b = jax.random.normal(jax.random.fold_in(key, 1), (n,))
    got = ops.trsv(l, b, trans=trans, implementation="pallas")
    want = ref.trsv_ref(l, b, trans=trans)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("n,r", [(128, 128), (256, 64), (384, 200), (129, 1)])
@pytest.mark.parametrize("trans", [False, True])
def test_trsv_matrix_rhs_matches_ref(n, r, trans):
    key = jax.random.PRNGKey(n * 7 + r)
    l = jnp.linalg.cholesky(_spd(key, n))
    b = jax.random.normal(jax.random.fold_in(key, 2), (n, r))
    got = ops.trsv(l, b, trans=trans, implementation="pallas")
    want = ref.trsv_ref(l, b, trans=trans)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# Blocked Cholesky (the lag-event refactorization)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n", [128, 256, 512, 100, 300])
def test_cholesky_matches_ref(n):
    k = _spd(jax.random.PRNGKey(n), n)
    got = ops.cholesky(k, implementation="pallas")
    want = ref.cholesky_ref(k)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=5e-4, atol=5e-4)


def test_cholesky_reconstructs():
    n = 384
    k = _spd(jax.random.PRNGKey(0), n)
    l = ops.cholesky(k, implementation="pallas")
    np.testing.assert_allclose(np.asarray(l @ l.T), np.asarray(k),
                               rtol=1e-3, atol=1e-3)


# ---------------------------------------------------------------------------
# Fused ops
# ---------------------------------------------------------------------------
def test_chol_append_matches_ref():
    n = 256
    key = jax.random.PRNGKey(3)
    l = jnp.linalg.cholesky(_spd(key, n))
    p = jax.random.normal(jax.random.fold_in(key, 1), (n,)) * 0.1
    c = jnp.asarray(3.0)
    q1, d1 = ops.chol_append(l, p, c, implementation="pallas")
    q2, d2 = ref.chol_append_ref(l, p, c)
    np.testing.assert_allclose(np.asarray(q1), np.asarray(q2), rtol=2e-4,
                               atol=2e-4)
    np.testing.assert_allclose(float(d1), float(d2), rtol=1e-5)


def test_gp_posterior_solve_matches_ref():
    n, m = 256, 33
    key = jax.random.PRNGKey(5)
    l = jnp.linalg.cholesky(_spd(key, n))
    resid = jax.random.normal(jax.random.fold_in(key, 1), (n,))
    k_star = jax.random.uniform(jax.random.fold_in(key, 2), (n, m))
    k_ss = jnp.full((m,), 2.0)
    m1, v1 = ops.gp_posterior_solve(l, resid, k_star, k_ss,
                                    implementation="pallas")
    m2, v2 = ref.gp_posterior_solve_ref(l, resid, k_star, k_ss)
    np.testing.assert_allclose(np.asarray(m1), np.asarray(m2), rtol=1e-3,
                               atol=1e-3)
    np.testing.assert_allclose(np.asarray(v1), np.asarray(v2), rtol=1e-3,
                               atol=1e-3)


# ---------------------------------------------------------------------------
# Envelope / fallback behaviour
# ---------------------------------------------------------------------------
def test_large_n_falls_back_to_xla():
    n = ops.MAX_PALLAS_N + 128
    k = jnp.eye(n) * 2.0
    # falls back, says so, and is still correct
    with pytest.warns(ops.KernelFallbackWarning, match="cholesky"):
        l = ops.cholesky(k, implementation="pallas")
    np.testing.assert_allclose(np.asarray(jnp.diag(l)),
                               np.full(n, np.sqrt(2.0)), rtol=1e-6)
