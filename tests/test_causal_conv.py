"""The short causal convolution with its SiLU: the two kernels (interpret
mode) against ``gdn.causal_conv`` + ``jax.nn.silu``, forward and every
gradient, across tile boundaries, and the delta layer on both paths.

Tolerances.  The kernels sum the taps in ``causal_conv``'s order, so the
sum before SiLU is the reference's to the last bit; SiLU's quotient is
the interpreter's against XLA's (1e-6 of the largest value read in
float32, 5e-6 asked; bfloat16 input reads exactly 0).  ``dx`` leaves in
the input's dtype: in bfloat16 a rounding that falls the other way is
2e-5 of the gradient's norm (1e-4 asked), in float32 1e-7 (5e-6 asked);
the taps' gradient is a sum over positions in the kernel's own order,
2e-7 of its norm in float32 (5e-6 asked).  A dropped tap or a halo off by
one position reads 1e-1 or more.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import gdn
from ray_tpu.models.transformer import TransformerConfig
from ray_tpu.ops import causal_conv as cc

ASKED = {"float32": 5e-6, "bfloat16": 1e-4}


def reference(x, taps):
    return jax.nn.silu(gdn.causal_conv(x[..., :taps.shape[-2]], taps))


def inputs(seed, x_shape, taps_shape, dtype):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jax.random.normal(keys[0], x_shape, jnp.float32).astype(dtype)
    taps = (0.5 * jax.random.normal(keys[1], taps_shape, jnp.float32)
            ).astype(dtype)
    dy = jax.random.normal(keys[2], (*x_shape[:-1], taps_shape[-2]),
                           jnp.float32)
    return x, taps, dy


def gap(got, want):
    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


# (x, taps, positions a tile): four tiles so that every halo is crossed
# both ways, one tile, two taps, the narrow channel block, the delta
# layer's read of the first 512 of every 768 columns
SHAPES = {
    "four_tiles": ((2, 512, 256), (256, 4), 128),
    "one_tile": ((2, 256, 256), (256, 4), 256),
    "two_taps": ((2, 384, 128), (128, 2), 128),
    "narrow_block": ((1, 256, 384), (384, 4), 128),
    "strided": ((2, 256, 4, 768), (4, 512, 4), 128),
}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_values_and_both_gradients_against_the_jnp_form(shape, dtype):
    x_shape, taps_shape, block_s = SHAPES[shape]
    x, taps, dy = inputs(1, x_shape, taps_shape, jnp.dtype(dtype))
    want, want_vjp = jax.vjp(reference, x, taps)
    got, got_vjp = jax.vjp(
        lambda x, t: cc.in_kernels(x, t, block_s, interpret=True), x, taps)
    assert got.dtype == jnp.float32 and got.shape == want.shape
    np.testing.assert_allclose(got, want, rtol=0, atol=5e-6 * float(
        jnp.max(jnp.abs(want))))
    for name, g, w in zip(("dx", "dtaps"), got_vjp(dy), want_vjp(dy)):
        assert g.dtype == w.dtype and g.shape == w.shape, name
        assert gap(g, w) < ASKED[dtype], (name, gap(g, w))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("shape", ["four_tiles", "one_tile", "strided"])
def test_a_bias_before_silu_and_its_gradient(shape, dtype):
    """The Mamba-2 layer's form, ``silu(conv(x) + bias)``: the bias as a
    column beside the taps, its gradient the sum of ``dpre`` over the
    row's positions; no bias is the form above."""
    x_shape, taps_shape, block_s = SHAPES[shape]
    x, taps, dy = inputs(4, x_shape, taps_shape, jnp.dtype(dtype))
    bias = jax.random.normal(jax.random.PRNGKey(5), taps_shape[:-1])

    def biased(x, t, b):
        return jax.nn.silu(gdn.causal_conv(x[..., :t.shape[-2]], t) + b)

    want, want_vjp = jax.vjp(biased, x, taps, bias)
    got, got_vjp = jax.vjp(lambda x, t, b: cc.in_kernels(
        x, t, block_s, interpret=True, bias=b), x, taps, bias)
    np.testing.assert_allclose(got, want, rtol=0, atol=5e-6 * float(
        jnp.max(jnp.abs(want))))
    for name, g, w in zip(("dx", "dtaps", "dbias"), got_vjp(dy),
                          want_vjp(dy)):
        assert g.dtype == w.dtype and g.shape == w.shape, name
        assert gap(g, w) < ASKED[dtype], (name, gap(g, w))
    np.testing.assert_array_equal(
        cc.causal_conv_silu(x, taps, bias), biased(x, taps, bias))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("shape", ["four_tiles", "strided"])
def test_gradients_through_a_following_reduction(shape, dtype):
    """As the delta layer follows it: an l2-norm over blocks of columns
    and a sum, differentiated by ``jax.grad`` (no cotangent handed in)."""
    x_shape, taps_shape, block_s = SHAPES[shape]
    x, taps, dy = inputs(2, x_shape, taps_shape, jnp.dtype(dtype))

    def loss(op, x, taps):
        y = op(x, taps)
        y = y.reshape(*y.shape[:-1], -1, 64)
        return jnp.sum(dy.reshape(y.shape) * y * jax.lax.rsqrt(
            jnp.sum(y * y, axis=-1, keepdims=True) + 1e-6))

    want = jax.grad(functools.partial(loss, reference), (0, 1))(x, taps)
    got = jax.grad(functools.partial(loss, lambda x, t: cc.in_kernels(
        x, t, block_s, interpret=True)), (0, 1))(x, taps)
    for name, g, w in zip(("dx", "dtaps"), got, want):
        assert gap(g, w) < ASKED[dtype], (name, gap(g, w))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_strided_read_is_slicing_first(dtype):
    """``[B, S, 4, 768]`` taking ``[:512]`` a head through the block
    index: what the kernels give for the sliced copy, to the bit (the
    taps' gradient to the order of the sum over tiles outside them), and
    zeros in the cotangent of the columns the filter skipped."""
    x, taps, dy = inputs(3, *SHAPES["strided"][:2], jnp.dtype(dtype))
    run = lambda x, t: cc.in_kernels(x, t, 128, interpret=True)
    got, got_vjp = jax.vjp(run, x, taps)
    want, want_vjp = jax.vjp(run, x[..., :512], taps)
    assert bool(jnp.array_equal(got, want))
    (dx, dtaps), (dx_sliced, dtaps_sliced) = got_vjp(dy), want_vjp(dy)
    assert dx.shape == x.shape
    assert bool(jnp.array_equal(dx[..., :512], dx_sliced))
    assert not bool(jnp.any(dx[..., 512:]))
    assert gap(dtaps, dtaps_sliced) < 1e-6


def test_a_tile_sees_the_rows_before_it_and_a_row_starts_from_zeros():
    """The second tile's first positions hold the first tile's last
    (noughting the first tile's input changes them), a row's first
    positions hold nothing of the row before it, and the split into
    tiles changes no value."""
    x, taps, _ = inputs(4, *SHAPES["four_tiles"][:2], jnp.float32)
    run = lambda x, block_s: cc.in_kernels(x, taps, block_s, interpret=True)
    whole, tiled = run(x, 512), run(x, 128)
    assert bool(jnp.array_equal(whole, tiled))
    cut = run(x.at[:, :128].set(0.0), 128)
    assert float(jnp.max(jnp.abs(cut[:, 128:131] - tiled[:, 128:131]))) > 1e-3
    assert bool(jnp.array_equal(cut[:, 131:], tiled[:, 131:]))
    other_row = run(x.at[0].set(0.0), 128)
    assert bool(jnp.array_equal(other_row[1], tiled[1]))


@pytest.mark.parametrize("case", ["kernels", "narrow_channels",
                                  "ragged_row", "off_the_tpu"])
def test_which_path_runs_is_read_from_the_input(case, monkeypatch):
    """``causal_conv_silu`` takes the kernels on a TPU at whole 128-lane
    blocks of channels and whole tiles of positions, and ``causal_conv``
    with XLA's SiLU otherwise; ``fallback_passes`` says which."""
    x_shape, taps_shape = {
        "kernels": ((2, 256, 2, 384), (2, 256, 4)),
        "narrow_channels": ((2, 256, 96), (96, 4)),
        "ragged_row": ((2, 200, 256), (256, 4)),
        "off_the_tpu": ((2, 256, 2, 384), (2, 256, 4)),
    }[case]
    if case != "off_the_tpu":
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    called = []

    def interpreted(x, taps, **bias):
        called.append(x.shape)
        return kernels(x, taps, interpret=True, **bias)

    kernels = cc.in_kernels
    monkeypatch.setattr(cc, "in_kernels", interpreted)
    x, taps, _ = inputs(5, x_shape, taps_shape, jnp.float32)
    got = cc.causal_conv_silu(x, taps)
    np.testing.assert_allclose(got, reference(x, taps), rtol=0, atol=5e-6)
    by_kernels = case == "kernels"
    assert called == ([x_shape] if by_kernels else [])
    assert cc.fallback_passes(x_shape, taps_shape) == (0 if by_kernels else 1)
    if case == "narrow_channels":
        with pytest.raises(ValueError, match="not the kernels' shapes"):
            kernels(x, taps, 128, interpret=True)


def _layer():
    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_heads=2, n_kv_heads=2, head_dim=16,
        d_ff=32, max_seq_len=256, dtype=jnp.float32,
        gdn=gdn.GDNConfig(num_key_heads=2, num_value_heads=4,
                          key_head_dim=64, value_head_dim=64),
        layer_pattern=(("gdn", "dense", 1),))
    lp = jax.tree.map(lambda x: x[0], gdn.init_gdn_params(
        jax.random.PRNGKey(6), 1, cfg.d_model, cfg.gdn, jnp.float32))
    h, dout = jax.random.normal(jax.random.PRNGKey(7),
                                (2, 2, 256, cfg.d_model))
    return cfg, lp, h, dout


def _run_layer(cfg, lp, h, dout):
    def run(lp):
        out, counted = gdn.gdn_attention(h, lp, cfg)
        return jnp.sum(out * dout), (out, counted)

    return jax.value_and_grad(run, has_aux=True)(lp)


@pytest.fixture(scope="module")
def layer_on_both_paths():
    """``gdn_attention`` (256 of every 384 columns a key head go through
    the filter) as it runs here, by ``causal_conv``, and with the
    convolution in the kernels, interpreted."""
    layer = _layer()
    fallback = _run_layer(*layer)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(cc, "kernels_by_default", lambda *shapes: True)
        patch.setattr(cc, "in_kernels", functools.partial(
            cc.in_kernels, interpret=True))
        kernels = _run_layer(*layer)
    return layer[1], fallback, kernels


def test_the_delta_layer_counts_the_path_it_took(layer_on_both_paths):
    _, ((_, (_, counted)), _), ((_, (_, counted_k)), _) = layer_on_both_paths
    assert float(counted["gdn_conv_fallback_passes"]) == 1.0
    assert float(counted_k["gdn_conv_fallback_passes"]) == 0.0
    for name in ("gdn_state_norm", "gdn_decay_mean", "gdn_beta_mean"):
        np.testing.assert_allclose(counted_k[name], counted[name], rtol=1e-5)


def test_the_delta_layer_gives_the_same_output_on_both_paths(
        layer_on_both_paths):
    _, ((_, (want, _)), _), ((_, (got, _)), _) = layer_on_both_paths
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-7)


@pytest.mark.parametrize("leaf", ["w_qkvz", "w_ba", "conv", "A_log",
                                  "dt_bias", "norm", "wo"])
def test_the_delta_layer_gives_the_same_gradients_on_both_paths(
        leaf, layer_on_both_paths):
    """Within ``tests/test_qwen3_next.py``'s tolerance for the layer's
    gradients, 1e-5 of a leaf's largest entry -- but for the two leaves
    of the decay: the two paths' SiLU differs in the last bit (1e-6 of
    ``mixed``), and at this draw a perturbation of ``mixed`` of that size
    (every entry times ``1 +- 1e-6``) moves ``A_log``'s and ``dt_bias``'s
    gradients by 3.7e-4 of their largest entry and every other leaf's by
    2e-6 (a head that remembers the whole row: PERF.md section 2); the
    kernels read 1.7e-4 there, 1e-3 asked."""
    lp, (_, want), (_, got) = layer_on_both_paths
    assert set(got) == set(lp)
    scale = float(jnp.max(jnp.abs(want[leaf])))
    assert scale > 0
    np.testing.assert_allclose(
        got[leaf] / scale, want[leaf] / scale,
        atol=1e-3 if leaf in ("A_log", "dt_bias") else 1e-5)
