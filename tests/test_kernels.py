"""Per-kernel allclose sweeps: Pallas (interpret mode) vs pure-jnp oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

KEY = jax.random.PRNGKey(42)


# ---------------------------------------------------------------------------
# lstm_cell
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("B,F,H", [(4, 16, 64), (97, 16, 256), (32, 20, 128),
                                   (1, 7, 32), (129, 16, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_lstm_cell(B, F, H, dtype):
    from repro.kernels.lstm_cell import ops, ref
    ks = jax.random.split(KEY, 6)
    x = jax.random.normal(ks[0], (B, F), dtype)
    h = jax.random.normal(ks[1], (B, H), dtype)
    c = jax.random.normal(ks[2], (B, H), dtype)
    wx = (jax.random.normal(ks[3], (F, 4 * H)) * 0.1).astype(dtype)
    wh = (jax.random.normal(ks[4], (H, 4 * H)) * 0.1).astype(dtype)
    b = (jax.random.normal(ks[5], (4 * H,)) * 0.1).astype(dtype)
    h2p, c2p = ops.lstm_cell(x, h, c, wx, wh, b)
    h2r, c2r = ref.lstm_cell_ref(x, h, c, wx, wh, b)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(h2p, np.float32),
                               np.asarray(h2r, np.float32), atol=tol, rtol=tol)
    np.testing.assert_allclose(np.asarray(c2p, np.float32),
                               np.asarray(c2r, np.float32), atol=tol, rtol=tol)


def test_lstm_cell_matches_policy_cell():
    """The kernel must be a drop-in for the policy's scan cell."""
    from repro.core.policy import lstm_cell_ref as policy_cell
    from repro.kernels.lstm_cell import ops
    ks = jax.random.split(KEY, 6)
    B, F, H = 8, 16, 64
    x = jax.random.normal(ks[0], (B, F))
    h = jax.random.normal(ks[1], (B, H))
    c = jax.random.normal(ks[2], (B, H))
    wx = jax.random.normal(ks[3], (F, 4 * H)) * 0.1
    wh = jax.random.normal(ks[4], (H, 4 * H)) * 0.1
    b = jax.random.normal(ks[5], (4 * H,)) * 0.1
    h2p, c2p = ops.lstm_cell(x, h, c, wx, wh, b)
    h2r, c2r = policy_cell(x, h, c, wx, wh, b)
    np.testing.assert_allclose(h2p, h2r, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(c2p, c2r, atol=1e-5, rtol=1e-5)
