"""The Pallas flash-attention kernel and its custom_vjp, in interpret
mode on the CPU, against ``full_attention`` and its ``jax.grad``.
``attention()`` takes the kernel only on a TPU, so nothing else in
tier-1 reaches it; chip_smoke.py asks the chip the same question at
full width."""

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.ops.flash_attention import attention, flash_attention
from ray_tpu.ops.ring_attention import full_attention

# Max abs error allowed on outputs and gradients of O(1) magnitude:
# f32 differs from the reference only in summation order; bf16 rounds
# inputs, probabilities and outputs to 8 bits of mantissa.
_TOL = {jnp.float32: 2e-5, jnp.bfloat16: 5e-2}


def _qkvd(dtype, B=2, L=256, H=2, D=64):
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    return [jax.random.normal(k, (B, L, H, D), jnp.float32).astype(dtype)
            for k in keys]


def _max_err(a, b):
    return float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                 - b.astype(jnp.float32))))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
def test_forward_matches_full_attention(dtype, causal):
    q, k, v, _ = _qkvd(dtype)
    got = flash_attention(q, k, v, causal=causal, interpret=True)
    want = full_attention(q, k, v, causal=causal)
    assert got.dtype == want.dtype and got.shape == want.shape
    assert _max_err(got, want) <= _TOL[dtype]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
def test_backward_matches_grad_of_full_attention(dtype, causal):
    q, k, v, dout = _qkvd(dtype)

    def scalar(fn):
        return lambda q, k, v: jnp.sum(
            fn(q, k, v).astype(jnp.float32) * dout.astype(jnp.float32))

    got = jax.grad(scalar(lambda q, k, v: flash_attention(
        q, k, v, causal=causal, interpret=True)), (0, 1, 2))(q, k, v)
    want = jax.grad(scalar(lambda q, k, v: full_attention(
        q, k, v, causal=causal)), (0, 1, 2))(q, k, v)
    for name, g, w in zip("qkv", got, want):
        assert g.dtype == w.dtype
        assert _max_err(g, w) <= _TOL[dtype], name


def test_unequal_blocks_and_train_step_shape():
    """block_q != block_k (the causal bound is a ceiling division) and
    value_and_grad straight through the kernel, as make_train_step
    takes it."""
    q, k, v, _ = _qkvd(jnp.float32, B=1, L=512, H=1)
    want = full_attention(q, k, v, causal=True)
    for bq, bk in ((256, 128), (128, 256)):
        got = flash_attention(q, k, v, causal=True, block_q=bq,
                              block_k=bk, interpret=True)
        assert _max_err(got, want) <= _TOL[jnp.float32], (bq, bk)
    loss, grads = jax.value_and_grad(
        lambda q: jnp.mean(flash_attention(q, k, v, interpret=True) ** 2))(q)
    assert jnp.isfinite(loss) and bool(jnp.all(jnp.isfinite(grads)))


def test_rejects_ragged_length_and_dispatch_off_chip():
    q, k, v, _ = _qkvd(jnp.float32, L=192)
    with pytest.raises(ValueError, match="multiple of the block sizes"):
        flash_attention(q, k, v, interpret=True)
    # Off the chip attention() is the reference, whatever the shape.
    assert _max_err(attention(q, k, v), full_attention(q, k, v)) == 0.0
