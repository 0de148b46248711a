"""ops/rmsnorm.py: the Pallas fused RMSNorm (VERDICT r3 #8 experiment).

Correctness gates for the A/B candidate: forward must match the jnp
reference bit-for-bit (same cast chain), the custom VJP must match
autodiff of the reference, and the train step must be swappable
without changing the loss.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kvedge_tpu.models.transformer import _rmsnorm
from kvedge_tpu.ops.rmsnorm import rmsnorm_fused


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("shape", [(4, 64, 128), (2, 8, 256), (5, 128)])
def test_forward_matches_reference_exactly(dtype, shape):
    x = jax.random.normal(jax.random.PRNGKey(0), shape, jnp.dtype(dtype))
    g = jax.random.normal(
        jax.random.PRNGKey(1), shape[-1:], jnp.float32
    ) * 0.1 + 1.0
    got = rmsnorm_fused(x, g)
    want = _rmsnorm(x, g)
    # Same fp32 mean-square, same cast chain: bitwise, not approximate.
    np.testing.assert_array_equal(
        np.asarray(got, np.float32), np.asarray(want, np.float32)
    )


def test_gradients_match_reference_autodiff():
    x = jax.random.normal(jax.random.PRNGKey(2), (4, 32, 128), jnp.float32)
    g = jax.random.normal(
        jax.random.PRNGKey(3), (128,), jnp.float32
    ) * 0.1 + 1.0

    def loss(fn):
        return lambda x, g: jnp.sum(jnp.square(fn(x, g)))

    gx, gg = jax.grad(loss(rmsnorm_fused), argnums=(0, 1))(x, g)
    rx, rg = jax.grad(loss(_rmsnorm), argnums=(0, 1))(x, g)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(rx),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(gg), np.asarray(rg),
                               rtol=1e-5, atol=1e-3)


def test_degenerate_row_count_falls_back():
    # 3 rows: no legal Pallas block; the jnp fallback must serve.
    x = jax.random.normal(jax.random.PRNGKey(4), (3, 64), jnp.float32)
    g = jnp.ones((64,), jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(rmsnorm_fused(x, g)), np.asarray(_rmsnorm(x, g))
    )


def test_train_step_swap_preserves_loss():
    """The A/B harness's patch point: a train step with the fused norm
    computes the same loss as the stock step."""
    import functools

    from kvedge_tpu.models import TransformerConfig, init_params, loss_fn
    from kvedge_tpu.models import transformer as tmod

    cfg = TransformerConfig(
        vocab=128, d_model=64, n_heads=4, n_layers=2, d_ff=128,
        max_seq=32, dtype="float32",
    )
    params = init_params(jax.random.PRNGKey(0), cfg)
    batch = jax.random.randint(
        jax.random.PRNGKey(1), (4, 33), 0, 128, jnp.int32
    )
    stock_loss = float(loss_fn(params, batch, cfg))
    stock = tmod._rmsnorm
    tmod._rmsnorm = rmsnorm_fused
    try:
        fused_loss = float(loss_fn(params, batch, cfg))
    finally:
        tmod._rmsnorm = stock
    assert abs(stock_loss - fused_loss) < 1e-5
