"""The Pallas flash-attention kernel and its custom_vjp, in interpret
mode on the CPU, against ``full_attention`` and its ``jax.grad``.
``attention()`` takes the kernel only on a TPU, so nothing else in
tier-1 reaches it; chip_smoke.py asks the chip the same question at
full width."""

import math
import re

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.models.transformer import TransformerConfig, remat_layer
from ray_tpu.ops.attention_mask import (CAUSAL, FULL, BlockDiffusion,
                                        SlidingWindow)
from ray_tpu.ops.flash_attention import (_FWD_BLOCKS, RESIDUAL_NAMES,
                                         _flash_forward, attention,
                                         flash_attention)
from ray_tpu.ops.ring_attention import full_attention

# Max abs error allowed on outputs and gradients of O(1) magnitude:
# f32 differs from the reference only in summation order; bf16 rounds
# inputs, probabilities and outputs to 8 bits of mantissa.
_TOL = {jnp.float32: 2e-5, jnp.bfloat16: 5e-2}


def _qkvd(dtype, B=2, L=256, H=2, D=64, kv_heads=None):
    """q, k, v, dout; k and v with ``kv_heads`` heads (default: H)."""
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    heads = (H, kv_heads or H, kv_heads or H, H)
    return [jax.random.normal(k, (B, L, h, D), jnp.float32).astype(dtype)
            for k, h in zip(keys, heads)]


def _mask(causal):
    return CAUSAL if causal else FULL


def _max_err(a, b):
    return float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                 - b.astype(jnp.float32))))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
def test_forward_matches_full_attention(dtype, causal):
    q, k, v, _ = _qkvd(dtype)
    got = flash_attention(q, k, v, mask=_mask(causal), interpret=True)
    want = full_attention(q, k, v, mask=_mask(causal))
    assert got.dtype == want.dtype and got.shape == want.shape
    assert _max_err(got, want) <= _TOL[dtype]


# (a) The forward at every tile of its ladder and at the one it chooses
# (512 here: L = 1,024, or two halves of 512 under block diffusion, so a
# row of tiles has unmasked tiles, masked ones and ones never visited).
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("kv_heads", [2, 1], ids=["mha", "grouped"])
@pytest.mark.parametrize("mask", [CAUSAL, FULL, BlockDiffusion(512, 4)],
                         ids=["causal", "full", "blockdiff"])
@pytest.mark.parametrize("block", [None, *_FWD_BLOCKS])
def test_forward_over_the_ladder_of_tiles(block, mask, kv_heads, dtype):
    q, k, v, _ = _qkvd(dtype, B=1, L=1024, H=2, kv_heads=kv_heads)
    got = flash_attention(q, k, v, mask=mask, block_q=block, block_k=block,
                          interpret=True)
    want = full_attention(q, k, v, mask=mask)
    assert got.dtype == want.dtype and got.shape == want.shape
    assert _max_err(got, want) <= _TOL[dtype]


@pytest.mark.parametrize("mask", [CAUSAL, FULL, BlockDiffusion(256, 4)],
                         ids=["causal", "full", "blockdiff"])
def test_lse_of_bfloat16_inputs_is_the_float32_log_sum_exp_of_them(mask):
    """(c) bfloat16 products are exact in float32 and the scale (128 **
    -0.5, no power of two) is applied to the float32 scores, so lse is
    the reference's up to summation order: rounding ``q * scale`` to
    bfloat16 would move it by thousandths."""
    q, k, _, _ = _qkvd(jnp.bfloat16, B=1, L=512, H=2, D=128, kv_heads=1)
    qh, kh = (x.transpose(0, 2, 1, 3).reshape(-1, 512, 128) for x in (q, k))
    _, lse = _flash_forward(qh, kh, kh, None, mask, None, None, True)
    assert lse.shape == (2, 512) and lse.dtype == jnp.float32
    pos = jnp.arange(512)
    scores = jnp.einsum("hqd,kd->hqk", qh.astype(jnp.float32),
                        kh[0].astype(jnp.float32),
                        precision="highest") * 128 ** -0.5
    want = jax.nn.logsumexp(jnp.where(
        mask.allowed(pos[:, None], pos[None, :]), scores, -jnp.inf), -1)
    assert _max_err(lse, want) <= _TOL[jnp.float32]


def _assert_grads_match(dtype, causal, shape=None, **blocks):
    """jax.grad through the kernel's custom_vjp (the Pallas backward in
    interpret mode) against jax.grad of ``full_attention``.  ``causal``
    is a flag or a mask description."""
    q, k, v, dout = _qkvd(dtype, **(shape or {}))
    mask = causal if not isinstance(causal, bool) else _mask(causal)

    def scalar(fn):
        return lambda q, k, v: jnp.sum(
            fn(q, k, v).astype(jnp.float32) * dout.astype(jnp.float32))

    got = jax.grad(scalar(lambda q, k, v: flash_attention(
        q, k, v, mask=mask, interpret=True, **blocks)),
        (0, 1, 2))(q, k, v)
    want = jax.grad(scalar(lambda q, k, v: full_attention(
        q, k, v, mask=mask)), (0, 1, 2))(q, k, v)
    for name, g, w in zip("qkv", got, want):
        assert g.dtype == w.dtype and g.shape == w.shape
        assert _max_err(g, w) <= _TOL[dtype], name


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
def test_backward_matches_grad_of_full_attention(dtype, causal):
    _assert_grads_match(dtype, causal)


# L = 640 is five backward blocks of 128 and L = 2048 four of 512 (the
# cell's block), so whole blocks lie above the diagonal and the masked
# and the unmasked loop both run; D = 128 is the cell's head size.
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal,shape", [
    (True, dict(B=1, L=640, H=2)),
    (False, dict(B=1, L=640, H=2)),
    (True, dict(B=1, L=2048, H=1)),
    (True, dict(B=1, L=256, H=2, D=128)),
    (False, dict(B=1, L=256, H=1, D=128)),
], ids=["causal-5x128", "full-5x128", "causal-4x512", "causal-d128",
        "full-d128"])
def test_backward_over_several_blocks_and_head_sizes(dtype, causal, shape):
    _assert_grads_match(dtype, causal, shape)


@pytest.mark.parametrize("block_q,block_k", [(256, 128), (128, 256)])
def test_backward_with_unequal_forward_blocks(block_q, block_k):
    """The forward's blocks shape the residuals' producer only: the
    backward picks its own and must agree whatever they were."""
    _assert_grads_match(jnp.float32, True, dict(B=1, L=512, H=1),
                        block_q=block_q, block_k=block_k)


def _equations(jaxpr, kernel=None):
    """(equation, name of the pallas_call it is inside or None) of every
    equation, sub-jaxprs included."""
    for eqn in jaxpr.eqns:
        yield eqn, kernel
        inner = kernel or (eqn.params["name"]
                           if eqn.primitive.name == "pallas_call" else None)
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _equations(sub, inner)


def test_gradient_is_pallas_kernels_and_no_loop_outside_them():
    q, k, v, _ = _qkvd(jnp.float32, B=1, L=512, H=1)
    jaxpr = jax.make_jaxpr(jax.grad(lambda q, k, v: jnp.sum(
        flash_attention(q, k, v, interpret=True)), (0, 1, 2)))(q, k, v)
    eqns = list(_equations(jaxpr.jaxpr))
    kernels = [eqn.params["name"] for eqn, kernel in eqns
               if eqn.primitive.name == "pallas_call" and not kernel]
    assert kernels[0] == "flash_attention_fwd" and len(kernels) >= 2
    assert all(n.startswith("flash_attention_bwd") for n in kernels[1:])
    loops = [eqn.primitive.name for eqn, kernel in eqns
             if eqn.primitive.name in ("scan", "while") and not kernel]
    assert loops == []


def _two_layer_gradient(mask, kv_heads, wrap, interpret=True):
    """jax.grad, by the carry and the stacked weights, of a two-layer
    scan of ``wrap(layer)``: each layer projects its input, calls the
    kernel and adds; with the arguments' shapes [B, L, H, D], [2, D, D]."""
    def layer(x, w):
        q = jnp.tanh(jnp.einsum("blhd,de->blhe", x, w))
        k = q[:, :, :kv_heads] + x[:, :, :kv_heads]
        return x + flash_attention(q, k, x[:, :, :kv_heads], mask=mask,
                                   interpret=interpret), None

    def loss(x, ws):
        return jnp.sum(jax.lax.scan(wrap(layer), x, ws)[0].astype(
            jnp.float32) ** 2)

    return jax.grad(loss, (0, 1))


def _keeping_the_residuals(layer):
    return remat_layer(layer, TransformerConfig(remat=True))


# The two shapes of the cells' attention at a small size: every head its
# own K/V under the causal mask, four query heads to a K/V head under
# block diffusion (2L = 256 positions).
_REMAT_CASES = [(CAUSAL, 2, 2), (BlockDiffusion(128, 4), 4, 1)]


@pytest.mark.parametrize("mask,heads,kv_heads", _REMAT_CASES,
                         ids=["causal", "blockdiff-grouped"])
def test_remat_keeps_the_residuals_so_the_forward_kernel_runs_once_a_layer(
        mask, heads, kv_heads):
    """The policy of ``remat_layer`` reaches the two names inside the
    custom_vjp's forward rule, inside ``flash_attention``'s own jit,
    under the scan: the backward scan's body has the backward kernel
    only.  A plain ``jax.checkpoint`` runs the forward kernel there
    again, to the same gradients bit for bit."""
    x = _qkvd(jnp.float32, B=1, L=256, H=heads)[0]
    ws = jax.random.normal(jax.random.PRNGKey(1), (2, 64, 64)) * 0.1
    calls, grads = {}, {}
    for how, wrap in (("kept", _keeping_the_residuals),
                      ("plain", jax.checkpoint)):
        fn = _two_layer_gradient(mask, kv_heads, wrap)
        kernels = [eqn.params["name"] for eqn, kernel in _equations(
            jax.make_jaxpr(fn)(x, ws).jaxpr)
            if eqn.primitive.name == "pallas_call" and not kernel]
        calls[how] = (kernels.count("flash_attention_fwd"),
                      kernels.count("flash_attention_bwd"))
        assert sum(calls[how]) == len(kernels)
        grads[how] = fn(x, ws)
    # one scan forward, one backward: a call in a body is a call a layer
    assert calls == {"kept": (1, 1), "plain": (2, 1)}
    for kept, plain in zip(grads["kept"], grads["plain"]):
        assert bool(jnp.all(jnp.isfinite(kept)))
        assert bool(jnp.array_equal(kept, plain))


def test_the_names_are_made_by_the_kernel_path_only():
    """``remat_layer`` without ``remat`` is the layer itself, and the
    jnp reference makes no names for the policy to keep."""
    def layer(x, w):
        return x, None
    assert remat_layer(layer, TransformerConfig(remat=False)) is layer
    q, k, v, _ = _qkvd(jnp.float32, B=1, L=128, H=1)
    named = {how: [eqn.params["name"] for eqn, _ in _equations(
        jax.make_jaxpr(jax.grad(lambda q: jnp.sum(fn(q, k, v))))(q).jaxpr)
        if eqn.primitive.name == "name"]
        for how, fn in (("kernel", lambda q, k, v: flash_attention(
            q, k, v, interpret=True)), ("reference", full_attention))}
    assert named == {"kernel": list(RESIDUAL_NAMES), "reference": []}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("mask", [CAUSAL, FULL, BlockDiffusion(256, 4)],
                         ids=["causal", "full", "blockdiff"])
def test_forward_products_take_the_input_dtype_and_accumulate_in_float32(
        dtype, mask):
    """(d) The MXU gets q, k, v (and p, cast to v's dtype) as they
    arrive; only the accumulation is float32."""
    q, k, v, _ = _qkvd(dtype, B=1, L=512, H=1)
    jaxpr = jax.make_jaxpr(lambda q, k, v: flash_attention(
        q, k, v, mask=mask, interpret=True))(q, k, v)
    dots = [eqn for eqn, kernel in _equations(jaxpr.jaxpr)
            if eqn.primitive.name == "dot_general"
            and kernel == "flash_attention_fwd"]
    # two products a tile, once for each range of tiles the mask gives
    assert len(dots) >= 2 and len(dots) % 2 == 0
    for eqn in dots:
        assert [x.aval.dtype for x in eqn.invars] == [dtype, dtype]
        assert eqn.params["preferred_element_type"] == jnp.float32
        assert eqn.outvars[0].aval.dtype == jnp.float32


# (block_q, block_k, L): equal and unequal tiles, the dense cell's
# (512 x 512 over 4,096) among them.
_CAUSAL_TILINGS = [
    (128, 128, 640), (512, 512, 4096), (128, 256, 1024), (256, 128, 1024),
    (128, 384, 768), (1024, 512, 4096), (512, 1024, 4096)]


@pytest.mark.parametrize("block_q,block_k,seq_len", _CAUSAL_TILINGS)
def test_causal_bounds_visit_the_blocks_at_or_below_the_diagonal(
        block_q, block_k, seq_len):
    """The helper the kernel's loop bounds come from, on Python ints:
    every pair with a visible entry is visited, none without one, and
    only pairs the diagonal crosses are masked."""
    num_q, num_k = seq_len // block_q, seq_len // block_k
    visited = 0
    for j in range(num_k):
        (first, unmasked, masked), (also, stop, plain) = CAUSAL.q_ranges(
            j, block_q, block_k, num_q)
        first, unmasked, also, stop = map(int, (first, unmasked, also, stop))
        assert masked and not plain and also == unmasked and stop == num_q
        assert 0 <= first <= unmasked <= num_q
        for i in range(num_q):
            sees_any = (i + 1) * block_q - 1 >= j * block_k
            sees_all = i * block_q >= (j + 1) * block_k - 1
            assert (i >= first) == sees_any, (i, j)
            assert (i >= unmasked) == sees_all, (i, j)
        visited += num_q - first
    if block_q == block_k:
        assert visited == num_q * (num_q + 1) // 2


@pytest.mark.parametrize("block_q,block_k,seq_len", _CAUSAL_TILINGS)
def test_causal_forward_ranges_visit_and_mask_exactly_the_tiles_they_must(
        block_q, block_k, seq_len):
    """(b) The forward's twin of the test above, from the Q side: a K
    tile is visited iff some row sees some key of it, and masked iff
    some row does not see every key of it."""
    num_q, num_k = seq_len // block_q, seq_len // block_k
    visited = masked = 0
    for i in range(num_q):
        visit = _ranges_cover(CAUSAL.k_ranges(i, block_q, block_k, num_k),
                              num_k)
        for j in range(num_k):
            sees_any = (i + 1) * block_q - 1 >= j * block_k
            sees_all = i * block_q >= (j + 1) * block_k - 1
            assert (j in visit) == sees_any, (i, j)
            if sees_any:
                assert visit[j] == (not sees_all), (i, j)
        visited += len(visit)
        masked += sum(visit.values())
    if block_q == block_k:
        assert visited == num_q * (num_q + 1) // 2 and masked == num_q
    if (block_q, block_k, seq_len) == (512, 512, 4096):
        assert (visited, masked) == (36, 8)      # the dense cell's


def test_unequal_blocks_and_train_step_shape():
    """block_q != block_k (the causal bound is a ceiling division) and
    value_and_grad straight through the kernel, as make_train_step
    takes it."""
    q, k, v, _ = _qkvd(jnp.float32, B=1, L=512, H=1)
    want = full_attention(q, k, v)
    for bq, bk in ((256, 128), (128, 256)):
        got = flash_attention(q, k, v, mask=CAUSAL, block_q=bq,
                              block_k=bk, interpret=True)
        assert _max_err(got, want) <= _TOL[jnp.float32], (bq, bk)
    loss, grads = jax.value_and_grad(
        lambda q: jnp.mean(flash_attention(q, k, v, interpret=True) ** 2))(q)
    assert jnp.isfinite(loss) and bool(jnp.all(jnp.isfinite(grads)))


def test_rejects_ragged_length_and_dispatch_off_chip():
    q, k, v, _ = _qkvd(jnp.float32, L=192)
    with pytest.raises(ValueError, match="multiple of the block sizes"):
        flash_attention(q, k, v, interpret=True)
    # The backward's blocks are its own (128 at least): a length only
    # the forward's blocks divide fails as loudly, not by falling back.
    with pytest.raises(ValueError, match="for the backward kernel"):
        jax.grad(lambda q: jnp.sum(flash_attention(
            q, k, v, block_q=64, block_k=64, interpret=True)))(q)
    # Off the chip attention() is the reference, whatever the shape.
    assert _max_err(attention(q, k, v), full_attention(q, k, v)) == 0.0


# --- masks by description and grouped K/V heads -------------------------

def _tile_has(mask, q_tile, k_tile, block_q, block_k):
    """(some pair allowed, every pair allowed) of one tile pair, by the
    elementwise predicate."""
    import numpy as np
    q_pos = q_tile * block_q + np.arange(block_q)[:, None]
    k_pos = k_tile * block_k + np.arange(block_k)[None, :]
    allowed = np.asarray(mask.allowed(jnp.asarray(q_pos), jnp.asarray(k_pos)))
    return bool(allowed.any()), bool(allowed.all())


def _ranges_cover(ranges, n):
    """tile -> masked flag, for the tiles the ranges visit; no tile is
    visited twice."""
    out = {}
    for first, stop, masked in ranges:
        for t in range(int(first), int(stop)):
            assert 0 <= t < n and t not in out, (t, ranges)
            out[t] = masked
    return out


@pytest.mark.parametrize("seq_len,block,block_q,block_k", [
    (32, 4, 8, 8), (32, 4, 16, 8), (32, 4, 8, 16), (64, 32, 16, 16),
    (64, 32, 8, 32), (64, 16, 16, 16), (32, 8, 4, 4), (32, 2, 8, 4)])
def test_block_diffusion_ranges_admit_exactly_the_tiles_with_an_allowed_pair(
        seq_len, block, block_q, block_k):
    """(b) Enumerated at small sizes: a tile pair is visited iff it holds
    an allowed pair, and flagged unmasked iff it holds no disallowed
    one -- from the Q side (forward) and from the K side (backward)."""
    mask = BlockDiffusion(seq_len, block)
    num_q, num_k = 2 * seq_len // block_q, 2 * seq_len // block_k
    pairs = 0
    for i in range(num_q):
        visit = _ranges_cover(mask.k_ranges(i, block_q, block_k, num_k),
                              num_k)
        for j in range(num_k):
            some, every = _tile_has(mask, i, j, block_q, block_k)
            assert (j in visit) == some, (i, j)
            if some:
                assert visit[j] == (not every), (i, j)
        pairs += len(visit)
    back = 0
    for j in range(num_k):
        visit = _ranges_cover(mask.q_ranges(j, block_q, block_k, num_q),
                              num_q)
        for i in range(num_q):
            some, every = _tile_has(mask, i, j, block_q, block_k)
            assert (i in visit) == some, (i, j)
            if some:
                assert visit[i] == (not every), (i, j)
        back += len(visit)
    assert pairs == back


def test_block_diffusion_visits_80_of_256_tiles_at_the_cell_size():
    mask = BlockDiffusion(4096, 4)
    n = 2 * 4096 // 512
    visited = [sum(int(stop) - int(first) for first, stop, _ in
                   ranges(t, 512, 512, n))
               for ranges in (mask.k_ranges, mask.q_ranges) for t in range(n)]
    assert sum(visited[:n]) == sum(visited[n:]) == 80 and n * n == 256
    # and of the 4 L^2 pairs the predicate admits L^2 + L B
    import numpy as np
    small = BlockDiffusion(64, 4)
    pos = jnp.arange(128)
    assert int(np.sum(np.asarray(small.allowed(pos[:, None], pos[None, :])))
               ) == 64 * 64 + 64 * 4
    with pytest.raises(ValueError, match="multiple or a divisor"):
        BlockDiffusion(96, 6).k_ranges(0, 16, 16, 12)
    with pytest.raises(ValueError, match="positions"):
        BlockDiffusion(64, 4).tile_span(64)


# (a) Forward and backward under the block-diffusion mask, query heads
# grouped 8 to a K/V head and ungrouped, block lengths 4 and 32, two
# tile sizes: 2L = 256 runs the backward in 128-tiles, 2L = 1024 in
# 512-tiles (the cell's), and the forward in 128- or 256-tiles.
@pytest.mark.parametrize("block", [4, 32])
@pytest.mark.parametrize("shape,blocks", [
    (dict(B=1, L=256, H=8, kv_heads=1), {}),
    (dict(B=2, L=256, H=2, kv_heads=2), {}),
    (dict(B=1, L=1024, H=8, kv_heads=1, D=128),
     dict(block_q=256, block_k=256)),
], ids=["grouped8-128", "ungrouped-128", "grouped8-512"])
def test_block_diffusion_forward_and_backward_match_the_dense_softmax(
        block, shape, blocks):
    mask = BlockDiffusion(shape["L"] // 2, block)
    q, k, v, _ = _qkvd(jnp.float32, **shape)
    got = flash_attention(q, k, v, mask=mask, interpret=True, **blocks)
    # the dense masked softmax, written out: K/V repeated per query head
    group = q.shape[2] // k.shape[2]
    pos = jnp.arange(q.shape[1])
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, jnp.repeat(k, group, 2)
                        ) * q.shape[-1] ** -0.5
    logits = jnp.where(mask.allowed(pos[:, None], pos[None, :]), logits,
                       -jnp.inf)
    want = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(logits, -1),
                      jnp.repeat(v, group, 2))
    assert _max_err(got, want) <= _TOL[jnp.float32]
    assert _max_err(full_attention(q, k, v, mask=mask), want) \
        <= _TOL[jnp.float32]
    _assert_grads_match(jnp.float32, mask, shape, **blocks)


@pytest.mark.parametrize("causal", [True, False])
def test_grouped_kv_heads_under_the_older_masks(causal):
    """dk and dv are summed over the group's query heads (bfloat16 as
    the cell runs it: the sum is kept in float32)."""
    _assert_grads_match(jnp.bfloat16, causal,
                        dict(B=2, L=256, H=4, kv_heads=2))
    _assert_grads_match(jnp.float32, causal,
                        dict(B=1, L=640, H=4, kv_heads=1))


# --- latent attention: unequal widths and one shared rotary key ---------

def _latent_operands(dtype, B=2, L=256, H=4, kv_heads=None, D=32, R=16,
                     Dv=48):
    """q [.., H, D], k [.., KV, D], v [.., KV, Dv], q_rope [.., H, R],
    k_rope [B, L, R] (one head a position), dout [.., H, Dv]."""
    kv = kv_heads or H
    shapes = [(B, L, H, D), (B, L, kv, D), (B, L, kv, Dv), (B, L, H, R),
              (B, L, R), (B, L, H, Dv)]
    keys = jax.random.split(jax.random.PRNGKey(2), len(shapes))
    return [jax.random.normal(k, shape, jnp.float32).astype(dtype)
            for k, shape in zip(keys, shapes)]


def _written_out(q, k, v, q_rope, k_rope, mask):
    """The masked softmax over both parts of the score, written out."""
    group = q.shape[2] // k.shape[2]
    pos = jnp.arange(q.shape[1])
    f32 = jnp.float32
    logits = (jnp.einsum("bqhd,bkhd->bhqk", q.astype(f32),
                         jnp.repeat(k, group, 2).astype(f32))
              + jnp.einsum("bqhr,bkr->bhqk", q_rope.astype(f32),
                           k_rope.astype(f32))
              ) * (q.shape[-1] + q_rope.shape[-1]) ** -0.5
    logits = jnp.where(mask.allowed(pos[:, None], pos[None, :]), logits,
                       -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(logits, -1),
                      jnp.repeat(v, group, 2).astype(f32))


# (a) Both kernels at unequal score and value widths with the shared
# rotary key: L = 256 runs 128-tiles (two heads' rows in the backward's
# scratch), L = 1024 the cells' 512-tiles; grouped K/V beside the
# latent model's one K/V head a query head.
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("mask,shape", [
    (CAUSAL, {}), (FULL, {}), (BlockDiffusion(128, 4), {}),
    (CAUSAL, dict(kv_heads=2)), (BlockDiffusion(128, 4), dict(kv_heads=1)),
    (CAUSAL, dict(B=1, L=1024, H=2, D=128, R=64, Dv=128)),
    (BlockDiffusion(512, 4), dict(B=1, L=1024, H=2, D=128, R=64, Dv=128)),
], ids=["causal", "full", "blockdiff", "causal-grouped", "blockdiff-grouped",
        "causal-512-cell-widths", "blockdiff-512-cell-widths"])
def test_unequal_widths_and_the_shared_rotary_key_match_jnp(mask, shape,
                                                            dtype):
    """The output and all five gradients, against the softmax written
    out (float32) and against ``full_attention`` with the same operands
    (what ``attention()`` falls back to)."""
    q, k, v, q_rope, k_rope, dout = _latent_operands(dtype, **shape)

    def kernel(q, k, v, q_rope, k_rope):
        return flash_attention(q, k, v, mask=mask, interpret=True,
                               q_rope=q_rope, k_rope=k_rope)

    def fallback(q, k, v, q_rope, k_rope):
        return full_attention(q, k, v, mask=mask, q_rope=q_rope,
                              k_rope=k_rope)

    got = kernel(q, k, v, q_rope, k_rope)
    assert got.dtype == dtype and got.shape == dout.shape
    want = fallback(q, k, v, q_rope, k_rope)
    assert _max_err(got, want) <= _TOL[dtype]
    if dtype == jnp.float32:
        assert _max_err(got, _written_out(q, k, v, q_rope, k_rope, mask)) \
            <= _TOL[dtype]

    def scalar(fn):
        return lambda *xs: jnp.sum(fn(*xs).astype(jnp.float32)
                                   * dout.astype(jnp.float32))

    grads = jax.grad(scalar(kernel), (0, 1, 2, 3, 4))(q, k, v, q_rope, k_rope)
    wants = jax.grad(scalar(fallback), (0, 1, 2, 3, 4))(q, k, v, q_rope,
                                                        k_rope)
    # the rotary key's gradient is a sum over the row's heads
    for name, g, w in zip(("q", "k", "v", "q_rope", "k_rope"), grads, wants):
        assert g.dtype == w.dtype and g.shape == w.shape, name
        assert _max_err(g, w) <= _TOL[dtype] * (
            q.shape[2] if name == "k_rope" and dtype == jnp.bfloat16
            else 1), name


def test_without_a_rotary_part_the_kernels_are_traced_as_they_were():
    """The rotary operands are refs of their own: a call without them
    traces no product, no scratch and no output for them, in either
    kernel, and the forward asks for no memory limit."""
    q, k, v, q_rope, k_rope, _ = _latent_operands(jnp.float32, B=1, H=2,
                                                  D=32, Dv=32)

    def products(**rope):
        jaxpr = jax.make_jaxpr(jax.grad(lambda q: jnp.sum(flash_attention(
            q, k, v, interpret=True, **rope))))(q)
        eqns = list(_equations(jaxpr.jaxpr))
        calls = {eqn.params["name"]: eqn for eqn, kernel in eqns
                 if eqn.primitive.name == "pallas_call" and not kernel}
        dots = {name: sum(1 for eqn, kernel in eqns if kernel == name
                          and eqn.primitive.name == "dot_general")
                for name in calls}
        return dots, {name: (len(eqn.invars), len(eqn.outvars))
                      for name, eqn in calls.items()}

    plain, plain_io = products()
    latent, latent_io = products(q_rope=q_rope, k_rope=k_rope)
    # two tile ranges under the causal mask: 2 products a forward tile
    # (3 with the rotary part), 5 a backward tile (8)
    assert plain == {"flash_attention_fwd": 4, "flash_attention_bwd": 10}
    assert latent == {"flash_attention_fwd": 6, "flash_attention_bwd": 16}
    assert plain_io == {"flash_attention_fwd": (3, 2),
                        "flash_attention_bwd": (6, 3)}
    assert latent_io == {"flash_attention_fwd": (5, 2),
                         "flash_attention_bwd": (8, 5)}
    with pytest.raises(ValueError, match="come together"):
        flash_attention(q, k, v, interpret=True, q_rope=q_rope)


def test_latent_attention_keeps_its_residuals_under_remat():
    """``RESIDUAL_NAMES`` under ``remat_layer`` with the rotary operands:
    the backward scan's body holds the backward kernel only."""
    q, k, v, q_rope, k_rope, _ = _latent_operands(jnp.float32, B=1, H=2,
                                                  D=32, Dv=32)
    ws = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 32)) * 0.1

    def layer(x, w):
        return x + flash_attention(
            jnp.tanh(jnp.einsum("blhd,de->blhe", x, w)), k, v,
            interpret=True, q_rope=q_rope, k_rope=k_rope), None

    def gradient(wrap):
        return jax.grad(lambda x, ws: jnp.sum(jax.lax.scan(
            wrap(layer), x, ws)[0] ** 2), (0, 1))

    calls, grads = {}, {}
    for how, wrap in (("kept", _keeping_the_residuals),
                      ("plain", jax.checkpoint)):
        kernels = [eqn.params["name"] for eqn, kernel in _equations(
            jax.make_jaxpr(gradient(wrap))(q, ws).jaxpr)
            if eqn.primitive.name == "pallas_call" and not kernel]
        calls[how] = (kernels.count("flash_attention_fwd"),
                      kernels.count("flash_attention_bwd"))
        grads[how] = gradient(wrap)(q, ws)
    assert calls == {"kept": (1, 1), "plain": (2, 1)}
    for kept, plain in zip(grads["kept"], grads["plain"]):
        assert bool(jnp.array_equal(kept, plain))


def _kernel_calls(text):
    """(forward, backward) Mosaic calls in a compiled program's text, by
    the kernels' names: the names a trace's events carry."""
    return tuple(len(re.findall(
        rf"%{name}[.\d]* = [^\n]*custom_call_target=\"tpu_custom_call\"",
        text)) for name in ("flash_attention_fwd", "flash_attention_bwd"))


@pytest.fixture(scope="module")
def one_v5e_chip():
    """A described (not attached) v5e for the chip's own compiler; made
    inside the fixture so that importing this file loads no libtpu."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def test_gradient_compiles_for_the_chip_at_the_cell_width(one_v5e_chip):
    """Mosaic takes both kernels at the benchmark cell's attention shape
    (4 x 4,096 tokens, 16 heads of 128, bfloat16): what interpret mode
    cannot say -- tiling, VMEM, the transposed-operand product."""
    x = jax.ShapeDtypeStruct((4, 4096, 16, 128), jnp.bfloat16,
                             sharding=one_v5e_chip)
    compiled = jax.jit(jax.grad(lambda q, k, v: jnp.sum(
        flash_attention(q, k, v).astype(jnp.float32)), (0, 1, 2))).lower(
            x, x, x).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 2
    assert _kernel_calls(text) == (1, 1)
    assert " while(" not in text


def test_block_diffusion_gradient_compiles_for_the_chip_at_the_cell_width(
        one_v5e_chip):
    """Both kernels at the block-diffusion cell's attention shape: 4 rows
    x 8,192 positions, 32 query heads on 4 K/V heads of 128, bfloat16."""
    q = jax.ShapeDtypeStruct((4, 8192, 32, 128), jnp.bfloat16,
                             sharding=one_v5e_chip)
    kv = jax.ShapeDtypeStruct((4, 8192, 4, 128), jnp.bfloat16,
                              sharding=one_v5e_chip)
    compiled = jax.jit(jax.grad(lambda q, k, v: jnp.sum(flash_attention(
        q, k, v, mask=BlockDiffusion(4096, 4)).astype(jnp.float32)),
        (0, 1, 2))).lower(q, kv, kv).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 2
    assert _kernel_calls(text) == (1, 1)
    assert " while(" not in text


def test_latent_attention_gradient_compiles_for_the_chip_at_the_cell_width(
        one_v5e_chip):
    """Both kernels at the latent-attention cell's shape: 2 rows x 8,192
    positions, 32 heads scoring over 128 + 64 columns against one
    rotary key a position, values over 128, bfloat16: K, V and the
    rotary key whole in the forward pass the 16 MB a kernel gets
    unasked (``_FWD_VMEM_BYTES``)."""
    def spec(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16,
                                    sharding=one_v5e_chip)
    wide = spec(2, 8192, 32, 128)
    compiled = jax.jit(jax.grad(
        lambda q, k, v, q_rope, k_rope: jnp.sum(flash_attention(
            q, k, v, q_rope=q_rope, k_rope=k_rope).astype(jnp.float32)),
        (0, 1, 2, 3, 4))).lower(
            wide, wide, wide, spec(2, 8192, 32, 64),
            spec(2, 8192, 64)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 2
    assert _kernel_calls(text) == (1, 1)
    assert " while(" not in text


def test_gated_attention_gradient_compiles_for_the_chip_at_the_cell_width(
        one_v5e_chip):
    """Both kernels at the hybrid cell's attention shape: 2 rows x 8,192
    positions, 16 query heads on 2 K/V heads of 256, bfloat16.  K and V
    whole are 16.8 MB double-buffered, over what a kernel gets unasked:
    the forward asks by the bytes it holds (``_FWD_UNASKED_BYTES``), no
    rotary pair in sight."""
    q = jax.ShapeDtypeStruct((2, 8192, 16, 256), jnp.bfloat16,
                             sharding=one_v5e_chip)
    kv = jax.ShapeDtypeStruct((2, 8192, 2, 256), jnp.bfloat16,
                              sharding=one_v5e_chip)
    gradient = jax.jit(jax.grad(lambda q, k, v: jnp.sum(flash_attention(
        q, k, v).astype(jnp.float32)), (0, 1, 2)))
    jaxpr = jax.make_jaxpr(gradient)(q, kv, kv).jaxpr
    limits = [eqn.params["compiler_params"]["mosaic_tpu"].vmem_limit_bytes
              for eqn, _ in _equations(jaxpr)
              if eqn.primitive.name == "pallas_call"]
    assert limits == [64 * 2 ** 20, 64 * 2 ** 20]
    text = gradient.lower(q, kv, kv).compile().as_text()
    assert text.count("tpu_custom_call") == 2
    assert _kernel_calls(text) == (1, 1)


def test_the_forward_asks_for_vmem_by_the_bytes_it_holds():
    """8 MB of K and V (the block-diffusion cell) is compiled as it was,
    12 MB with the rotary key (the latent cell) and 16.8 MB of 256-wide
    K and V ask."""
    def limit(q, kv, v=None, rope=None):
        shapes = [jax.ShapeDtypeStruct(s, jnp.bfloat16)
                  for s in (q, kv, v or kv) + (rope or ())]

        def call(q, k, v, *rope):
            return flash_attention(q, k, v, **dict(zip(("q_rope", "k_rope"),
                                                       rope)))
        (eqn,) = [e for e, _ in _equations(jax.make_jaxpr(call)(*shapes).jaxpr)
                  if e.primitive.name == "pallas_call"]
        return eqn.params["compiler_params"]["mosaic_tpu"].vmem_limit_bytes

    assert limit((4, 4096, 16, 128), (4, 4096, 16, 128)) is None
    assert limit((4, 8192, 32, 128), (4, 8192, 4, 128)) is None
    assert limit((2, 8192, 32, 128), (2, 8192, 32, 128),
                 rope=((2, 8192, 32, 64), (2, 8192, 64))) == 64 * 2 ** 20
    assert limit((2, 8192, 16, 256), (2, 8192, 2, 256)) == 64 * 2 ** 20


def _delta_calls(text):
    return tuple(len(re.findall(
        rf"%{name}[.\d]* = [^\n]*custom_call_target=\"tpu_custom_call\"",
        text)) for name in ("gated_delta_fwd", "gated_delta_bwd"))


def _keeping_the_rules_residuals(f):
    from ray_tpu.ops.gated_delta import RESIDUAL_NAMES
    return jax.checkpoint(
        f, policy=jax.checkpoint_policies.save_only_these_names(
            *RESIDUAL_NAMES))


@pytest.mark.parametrize("wrap,calls", [
    (lambda f: f, (1, 1)), (_keeping_the_rules_residuals, (1, 1)),
    (jax.checkpoint, (2, 1))], ids=["kept", "named", "remat"])
@pytest.mark.parametrize("key_heads", [32, 16])
def test_gated_delta_gradient_compiles_for_the_chip_at_the_cell_width(
        one_v5e_chip, key_heads, wrap, calls):
    """Mosaic takes both fused kernels at the hybrid cell's delta layers'
    shape (2 rows x 8,192 positions, 32 value heads of 128 x 128, chunks
    of 64, bfloat16) with q and k a value head and a key head: kept in
    this file because one process may describe the chip
    (``one_v5e_chip``).  What the compiled programs hold in HBM: no
    float32 array that ends in a chunk's ``[64, 64]`` (nor a tile's
    ``[128, 128]`` a pair of chunks) but the state entering each grid
    step of 8 chunks, written by the one forward kernel and read by
    ``gated_delta_bwd``, and the row's last; no state a chunk.  Under a
    checkpoint that saves the rule's two names (``remat_layer``) the
    forward kernel runs once; under one that saves nothing, twice."""
    from ray_tpu.ops.gated_delta import gated_delta_rule

    def spec(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_v5e_chip)

    keyed, wide = spec((2, 8192, key_heads, 128)), spec((2, 8192, 32, 128))
    gate = spec((2, 8192, 32), jnp.float32)
    operands = (keyed, keyed, wide, gate, gate)
    every_chunk, steps = "f32[64,128,128,128]", "f32[64,16,128,128]"
    # the state entering each grid step, and a row's last entering state
    # as the kernel writes it and as the rule returns it: the float32
    # arrays the rule may hold that end in a square (Dk x Dv is a tile's
    # size)
    states = {steps, "f32[64,128,128]", "f32[2,32,128,128]"}

    def squares(text):
        return {m.group(1) for m in re.finditer(
            r"(f32\[(?:\d+,)*(?:64,64|128,128)\])\{", text)}

    def rule(*xs):
        return gated_delta_rule(*xs, use_pallas=True)

    forward = jax.jit(rule).lower(*operands).compile().as_text()
    assert _delta_calls(forward) == (1, 0)
    assert forward.count("tpu_custom_call") == 1
    assert squares(forward) <= states

    # the value too, so that remat's forward pass has a reader
    text = jax.jit(jax.value_and_grad(lambda *xs: jnp.sum(wrap(rule)(
        *xs).astype(jnp.float32)), (0, 1, 2, 3, 4))).lower(
            *operands).compile().as_text()
    assert _delta_calls(text) == calls
    assert text.count("tpu_custom_call") == sum(calls)
    assert " while(" not in text
    assert every_chunk not in text
    # (XLA may fetch the kept step states for the backward kernel a
    # slice of the heads at a time)
    assert steps in squares(text) and all(
        shape in states or re.fullmatch(r"f32\[\d+,16,128,128\]", shape)
        for shape in squares(text))
    writers = {line.split(" = ")[0].strip().split(".")[0]
               for line in text.splitlines() if "tpu_custom_call" in line
               and steps in line.split(" custom-call(")[0]}
    assert writers == {"%gated_delta_fwd"}


def test_kda_gradient_compiles_for_the_chip_at_the_cell_width(one_v5e_chip):
    """Mosaic takes both KDA kernels at the Ling cell's KDA layers' shape
    (2 rows x 8,192 positions, 32 heads of 128 x 128, chunks of 64,
    bfloat16, g float32): tiles of two chunks (beta handed a 128-row
    tile a row), VMEM, the transposed products.  What the compiled
    program holds in HBM: no float32 state a chunk, and the state
    entering each grid step of four chunks written by ``kda_fwd``
    alone."""
    from ray_tpu.ops.kda import kda_rule

    def spec(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_v5e_chip)

    wide = spec((2, 8192, 32, 128))
    operands = (wide, wide, wide, spec((2, 8192, 32, 128), jnp.float32),
                spec((2, 8192, 32), jnp.float32))
    text = jax.jit(jax.value_and_grad(lambda *xs: jnp.sum(kda_rule(
        *xs, use_pallas=True).astype(jnp.float32)), (0, 1, 2, 3, 4))).lower(
            *operands).compile().as_text()
    calls = [line for line in text.splitlines()
             if "tpu_custom_call" in line]
    assert sorted(re.match(r"\s*%(\w+)[.\d]* = ", line).group(1)
                  for line in calls) == ["kda_bwd", "kda_fwd"]
    assert " while(" not in text
    assert "f32[64,128,128,128]" not in text
    steps = "f32[64,32,128,128]"
    writers = {re.match(r"\s*%(\w+)", line).group(1) for line in calls
               if steps in line.split(" custom-call(")[0]}
    assert writers == {"kda_fwd"}
    (forward,) = [line for line in calls if "%kda_fwd" in line]
    assert "f32[64,64,128]" in forward.split("operand_layout_constraints")[1]


@pytest.mark.parametrize("how,wrap,want", [
    ("kept", _keeping_the_residuals, (1, 1)),
    ("plain", jax.checkpoint, (2, 1))])
@pytest.mark.parametrize("mask,q_shape,kv_heads", [
    (CAUSAL, (4, 4096, 16, 128), 16),
    (BlockDiffusion(4096, 4), (4, 8192, 32, 128), 4)],
    ids=["dense-cell", "blockdiff-cell"])
def test_remat_scan_compiles_for_the_chip_with_one_forward_kernel_a_layer(
        one_v5e_chip, mask, q_shape, kv_heads, how, wrap, want):
    """What the chip's compiler is handed at both cells' attention
    shapes: the gradient of a two-layer scan holds the forward kernel
    once (in the forward loop's body) where remat keeps ``out`` and
    ``lse``, and a second time in the backward loop's where it does
    not."""
    x = jax.ShapeDtypeStruct(q_shape, jnp.bfloat16, sharding=one_v5e_chip)
    ws = jax.ShapeDtypeStruct((2, 128, 128), jnp.bfloat16,
                              sharding=one_v5e_chip)
    text = jax.jit(_two_layer_gradient(mask, kv_heads, wrap,
                                       interpret=False)).lower(
        x, ws).compile().as_text()
    assert _kernel_calls(text) == want
    assert text.count("tpu_custom_call") == sum(want)
    assert text.count(" while(") == 2


# --- the sliding window ---------------------------------------------------

def test_the_window_counts_the_querys_own_position():
    """Query ``i`` sees keys ``i - w + 1 .. i``: ``w`` of them once the
    row is that long, and ``L w - w (w - 1) / 2`` pairs in all."""
    import numpy as np
    mask = SlidingWindow(4)
    pos = jnp.arange(16)
    allowed = np.asarray(mask.allowed(pos[:, None], pos[None, :]))
    assert allowed[7].nonzero()[0].tolist() == [4, 5, 6, 7]
    assert allowed[2].nonzero()[0].tolist() == [0, 1, 2]
    assert int(allowed.sum()) == 16 * 4 - 4 * 3 // 2
    assert mask.tile_span(16) == 16 and mask != CAUSAL
    assert SlidingWindow(4) == SlidingWindow(4)
    with pytest.raises(ValueError, match="window"):
        SlidingWindow(0)


@pytest.mark.parametrize("window,block_q,block_k", [
    (4, 8, 8), (8, 8, 8), (12, 8, 8), (24, 8, 8), (8, 16, 8), (8, 8, 16),
    (5, 4, 8), (64, 8, 8), (1, 8, 8), (9, 8, 4)])
def test_window_ranges_admit_exactly_the_tiles_with_an_allowed_pair(
        window, block_q, block_k):
    """Against the brute-force table, for windows below, at and above a
    tile and past the row: a tile pair is visited iff it holds an
    allowed pair and flagged unmasked iff it holds no disallowed one,
    from the Q side (forward) and from the K side (backward)."""
    mask = SlidingWindow(window)
    num_q, num_k = 32 // block_q, 32 // block_k
    pairs = back = 0
    for i in range(num_q):
        visit = _ranges_cover(mask.k_ranges(i, block_q, block_k, num_k),
                              num_k)
        for j in range(num_k):
            some, every = _tile_has(mask, i, j, block_q, block_k)
            assert (j in visit) == some, (i, j)
            if some:
                assert visit[j] == (not every), (i, j)
        pairs += len(visit)
    for j in range(num_k):
        visit = _ranges_cover(mask.q_ranges(j, block_q, block_k, num_q),
                              num_q)
        for i in range(num_q):
            some, every = _tile_has(mask, i, j, block_q, block_k)
            assert (i in visit) == some, (i, j)
            if some:
                assert visit[i] == (not every), (i, j)
        back += len(visit)
    assert pairs == back


def test_a_window_of_a_tile_visits_two_masked_tiles_a_tile():
    """At 512-tiles and a window of 512 (the cell's) a Q tile visits the
    tile before it and its own, both masked: 63 of the 528 tiles the
    causal mask visits at 16,384 positions."""
    mask, n = SlidingWindow(512), 16384 // 512
    for ranges in (mask.k_ranges, mask.q_ranges):
        for t in range(n):
            visit = _ranges_cover(ranges(t, 512, 512, n), n)
            near = {t - 1, t} if ranges == mask.k_ranges else {t, t + 1}
            assert set(visit) == {x for x in near if 0 <= x < n}
            assert all(visit.values())
    assert sum(len(_ranges_cover(mask.k_ranges(t, 512, 512, n), n))
               for t in range(n)) == 63


@pytest.mark.parametrize("window", [40, 128, 200])
@pytest.mark.parametrize("shape", [
    dict(B=2, L=256, H=2), dict(B=1, L=512, H=4, kv_heads=2)],
    ids=["mha", "grouped"])
def test_window_forward_and_backward_match_full_attention(window, shape):
    """Both kernels under windows below, at and above their 128-tiles,
    grouped K/V too, against ``full_attention`` and its ``jax.grad``."""
    mask = SlidingWindow(window)
    q, k, v, _ = _qkvd(jnp.float32, **shape)
    got = flash_attention(q, k, v, mask=mask, interpret=True,
                          block_q=128, block_k=128)
    assert _max_err(got, full_attention(q, k, v, mask=mask)) \
        <= _TOL[jnp.float32]
    # and it is not the causal answer
    assert _max_err(got, full_attention(q, k, v)) > 1e-3
    _assert_grads_match(jnp.float32, mask, shape)


@pytest.mark.parametrize("heads,window", [(9, 160), (6, 128), (9, 640),
                                          (6, None)],
                         ids=["9-band", "6-band", "9-row", "6-causal"])
def test_groups_of_nine_and_six_at_128_columns_match_full_attention(
        heads, window):
    """Both kernels with 9 and 6 query heads a K/V head (no power of
    two: the backward's grid runs a group's heads in turn and sums
    ``dk``/``dv`` over them) at 128 | 128 columns over five 128-tiles
    each way, under windows above a tile, at one, as long as the row,
    and causally, against ``full_attention`` and its ``jax.grad``."""
    mask = CAUSAL if window is None else SlidingWindow(window)
    shape = dict(B=1, L=640, H=3 * heads, D=128, kv_heads=3)
    q, k, v, _ = _qkvd(jnp.float32, **shape)
    got = flash_attention(q, k, v, mask=mask, interpret=True,
                          block_q=128, block_k=128)
    assert _max_err(got, full_attention(q, k, v, mask=mask)) \
        <= _TOL[jnp.float32]
    if window is not None and window < 640:
        assert _max_err(got, full_attention(q, k, v)) > 1e-3
    # a query head reads its own K/V head: head 2 * heads is the third's
    other = full_attention(q[:, :, 2 * heads:2 * heads + 1], k[:, :, :1],
                           v[:, :, :1], mask=mask)
    assert _max_err(got[:, :, 2 * heads:2 * heads + 1], other) > 1e-3
    _assert_grads_match(jnp.float32, mask, shape)


def test_window_and_full_gradients_compile_for_the_chip_at_the_cell_width(
        one_v5e_chip):
    """Both kernels at the two attention shapes of the cell whose layers
    are of two kinds (1 row x 16,384 positions, 128 | 128 columns over 8
    K/V heads, bfloat16): 72 query heads under the window of 512 and 48
    under the causal mask.  K and V whole are 16.8 MB double-buffered in
    the forward, as at 256-wide heads over 8,192 positions."""
    def spec(heads):
        return jax.ShapeDtypeStruct((1, 16384, heads, 128), jnp.bfloat16,
                                    sharding=one_v5e_chip)

    for heads, mask in ((72, SlidingWindow(512)), (48, CAUSAL)):
        text = jax.jit(jax.grad(lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, mask=mask).astype(jnp.float32)), (0, 1, 2))).lower(
                spec(heads), spec(8), spec(8)).compile().as_text()
        assert _kernel_calls(text) == (1, 1)
        assert text.count("tpu_custom_call") == 2


def test_differential_attention_gradient_compiles_for_the_chip_at_the_cell_width(
        one_v5e_chip):
    """Both kernels at the decoder-hybrid-decoder cell's attention shape
    (1 row x 16,384 positions, 20 query heads of 64 over 10 key heads of
    64 and value heads of 128, bfloat16) under the window of 512 and
    under the causal mask: K and V whole a head fit at these widths."""
    def spec(heads, width):
        return jax.ShapeDtypeStruct((1, 16384, heads, width), jnp.bfloat16,
                                    sharding=one_v5e_chip)

    for mask in (SlidingWindow(512), CAUSAL):
        text = jax.jit(jax.grad(lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, mask=mask).astype(jnp.float32)), (0, 1, 2))).lower(
                spec(20, 64), spec(10, 64), spec(10, 128)).compile().as_text()
        assert _kernel_calls(text) == (1, 1)
        assert text.count("tpu_custom_call") == 2


def test_selective_scan_gradient_compiles_for_the_chip_at_the_cell_width(
        one_v5e_chip):
    """Mosaic takes both scan kernels at the cell's Mamba layers' shape
    (1 row x 16,384 positions, 5,120 channels x 16 states, chunks of
    64): kept in this file because one process may describe the chip.
    What the compiled programs hold in HBM: no array of ``[positions,
    channels, states]`` in either direction; under the gradient the
    entering states a chunk, written by the ``fwd`` rule's kernel."""
    from ray_tpu.ops.selective_scan import selective_scan

    def spec(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_v5e_chip)

    operands = (spec((1, 16384, 5120), jnp.bfloat16), spec((1, 16384, 5120)),
                spec((5120, 16)), spec((1, 16384, 16)), spec((1, 16384, 16)),
                spec((5120,)))

    def calls(text):
        return tuple(len(re.findall(
            rf"%{name}[.\d]* = [^\n]*custom_call_target=\"tpu_custom_call\"",
            text)) for name in ("selective_scan_fwd", "selective_scan_bwd"))

    def rule(*xs):
        return selective_scan(*xs, use_pallas=True)

    def every_state(text):
        # [.., 16384, .., 16, ..] or [.., 16384, 5120 or 40 x 128, .., 16]
        return re.findall(r"f32\[(?:\d+,)*16384,(?:5120,16|16,5120|"
                          r"16,40,128|40,128,16)\]", text)

    forward = jax.jit(rule).lower(*operands).compile().as_text()
    assert calls(forward) == (1, 0) and not every_state(forward)
    text = jax.jit(jax.value_and_grad(lambda *xs: jnp.sum(rule(*xs).astype(
        jnp.float32)), (0, 1, 2, 3, 4, 5))).lower(
            *operands).compile().as_text()
    assert calls(text) == (1, 1) and not every_state(text)
    # the entering states: 256 chunks of [16, 40, 128]
    assert "f32[1,256,16,40,128]" in text


def test_causal_conv_gradient_compiles_for_the_chip_at_the_cell_width(
        one_v5e_chip):
    """Mosaic takes both kernels of the delta layers' convolution at the
    hybrid cell's shape (2 rows x 8,192 positions, 16 key heads of 768
    columns of which 512 are filtered, 4 taps, bfloat16): the packed
    rotation, the block index that skips ``z``, the lane-sparse store of
    the taps' sums.  Kept in this file because one process may describe
    the chip.  What the compiled program no longer holds: the padded
    float32 copy (8,195 positions) and any float32 array of the
    projection's whole width."""
    from ray_tpu.ops import causal_conv as cc

    def spec(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_v5e_chip)

    def calls(text):
        return tuple(len(re.findall(
            rf"%\w*{name}[\w.]* = [^\n]*custom_call_target=\"tpu_custom_call\"",
            text)) for name in ("causal_conv_fwd", "causal_conv_bwd"))

    op = cc.in_kernels

    operands = (spec((2, 8192, 16, 768)), spec((16, 512, 4)))
    forward = jax.jit(op).lower(*operands).compile().as_text()
    assert calls(forward) == (1, 0)
    text = jax.jit(lambda x, taps, dy: jax.vjp(op, x, taps)[1](dy)).lower(
        *operands, spec((2, 8192, 16, 512), jnp.float32)).compile().as_text()
    assert calls(text) == (0, 1)
    for compiled in (forward, text):
        assert "8195" not in compiled
        assert not re.findall(r"f32\[2,(?:8192,12288|12288,8192|"
                              r"8192,16,768)\]", compiled)


def test_mamba2_mixer_compiles_for_the_chip_with_no_copy_around_the_rule(
        one_v5e_chip, monkeypatch):
    """One Mamba-2 mixer at the Mamba-2 cell's widths (1 x 8,192
    positions, d 4,096, 128 heads of 64 over 8 groups of 128 states,
    bfloat16), forward and backward under the kernels' remat policy:
    ``ops/ssd.py``'s two kernels compile, the forward runs once, and the
    compiled program holds no copy of the rule's operands or results (x,
    B, C, y or a cotangent of one: 16 MB and more) -- the rule reads and
    writes them where the convolution and the norm have them.  (What
    copies are left: the convolution's taps, and the step sizes' 4 MB
    retiled once, where the backward makes them again.)"""
    import types
    from ray_tpu.models import mamba2, remat
    from ray_tpu.models.common import LayerCall
    m = mamba2.Mamba2Config(num_heads=128, head_dim=64, n_groups=8,
                            state_size=128, norm_groups=8)
    cfg = types.SimpleNamespace(mamba2=m, d_model=4096, dtype=jnp.bfloat16,
                                norm_eps=1e-5)
    lp = jax.tree.map(
        lambda v: jax.ShapeDtypeStruct(v.shape[1:], v.dtype,
                                       sharding=one_v5e_chip),
        jax.eval_shape(lambda k: mamba2._init(k, 1, cfg, {}),
                       jax.random.PRNGKey(0)))
    h = jax.ShapeDtypeStruct((1, 8192, 4096), jnp.bfloat16,
                             sharding=one_v5e_chip)
    layer = jax.checkpoint(
        lambda h, lp: mamba2._mamba2(h, lp, LayerCall(cfg, "mamba2"))[0],
        policy=jax.checkpoint_policies.save_only_these_names(
            *remat.BASE_NAMES))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    text = jax.jit(jax.grad(lambda h, lp: jnp.sum(jnp.square(
        layer(h, lp).astype(jnp.float32))), (0, 1))).lower(
            h, lp).compile().as_text()
    calls = {name: len(re.findall(
        rf"%{name}[.\d]* = [^\n]*custom_call_target=\"tpu_custom_call\"",
        text)) for name in ("ssd_fwd", "ssd_bwd")}
    assert calls == {"ssd_fwd": 1, "ssd_bwd": 1}
    entry = text[text.index("\nENTRY "):]
    copies = []
    for kind, dims in re.findall(
            r"%copy[.\d]* = (bf16|f32)\[([\d,]+)\]\S* copy\(", entry):
        size = math.prod(int(d) for d in dims.split(","))
        if size * (2 if kind == "bf16" else 4) >= 16 * 2 ** 20:
            copies.append((kind, dims))
    assert copies == [], copies
