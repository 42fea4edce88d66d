"""Pallas flash-attention kernel for a single TPU chip.

The hot op of the model stack: blockwise attention with running-max
softmax so the [L, L] score matrix never leaves VMEM.  MXU-aligned 128
blocks, f32 accumulation, bf16-friendly inputs.  (Pallas guide: grid +
BlockSpec pattern; preferred_element_type for MXU dots.)

Differentiable through a ``custom_vjp``: the forward kernel also emits
the per-row log-sum-exp, and the backward recomputes the probabilities
blockwise in ``jax.numpy`` from (q, k, v, out, lse) — the [L, L] matrix
is never materialized in either direction.  (A Pallas backward, bf16
dots and K/V tiling are ROADMAP S3.)

``attention()`` picks the kernel on TPU and the jnp reference
(ops.ring_attention.full_attention) elsewhere; tests run the kernel in
Pallas interpret mode.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_NEG_INF = -1e30
_LANES = 128


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, block_k: int,
                  causal: bool, scale: float, seq_len: int):
    # q_ref/o_ref: [block_q, D]; k_ref/v_ref: [L, D];
    # lse_ref: [block_q, _LANES] (row value broadcast along lanes).
    block_q = q_ref.shape[0]
    d = q_ref.shape[1]
    q_blk = pl.program_id(1)
    q = q_ref[:].astype(jnp.float32) * scale

    def body(i, carry):
        acc, m_i, l_i = carry
        k = k_ref[pl.ds(i * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[pl.ds(i * block_k, block_k), :].astype(jnp.float32)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)
        if causal:
            q_pos = q_blk * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = i * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
        m_new = jnp.maximum(m_i, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_i - m_new)
        l_new = l_i * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * corr + jnp.dot(p, v, preferred_element_type=jnp.float32)
        return acc, m_new, l_new

    num_k = seq_len // block_k
    if causal:
        # Only blocks at or before this q block contribute.
        num_k = jnp.minimum(num_k, pl.cdiv((q_blk + 1) * block_q, block_k))
    acc0 = jnp.zeros((block_q, d), jnp.float32)
    m0 = jnp.full((block_q, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q, 1), jnp.float32)
    acc, m, l = jax.lax.fori_loop(0, num_k, body, (acc0, m0, l0))
    l = jnp.maximum(l, 1e-20)
    o_ref[:] = (acc / l).astype(o_ref.dtype)
    lse_ref[:] = jnp.broadcast_to(m + jnp.log(l), (block_q, _LANES))


def _flash_forward(qh, kh, vh, causal, block_q, block_k, interpret):
    """[BH, L, D] x3 -> (out [BH, L, D], lse [BH, L] f32)."""
    BH, L, D = qh.shape
    if L % block_q or L % block_k:
        raise ValueError(
            f"sequence length {L} must be a multiple of the block sizes "
            f"({block_q}, {block_k}); pad upstream")
    kernel = functools.partial(_flash_kernel, block_k=block_k,
                               causal=causal, scale=D ** -0.5, seq_len=L)
    out, lse = pl.pallas_call(
        kernel,
        grid=(BH, L // block_q),
        in_specs=[
            pl.BlockSpec((None, block_q, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, L, D), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((None, L, D), lambda b, i: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, block_q, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, block_q, _LANES), lambda b, i: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, L, D), qh.dtype),
            jax.ShapeDtypeStruct((BH, L, _LANES), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention_fwd",
    )(qh, kh, vh)
    return out, lse[:, :, 0]


@jax.named_scope("flash_attention_bwd")
def _flash_backward(qh, kh, vh, out, lse, dout, causal, block_k):
    """Blockwise jnp backward over K/V blocks (scan): per block, the
    probabilities are recomputed from the saved log-sum-exp, so peak
    extra memory is one [BH, L, block_k] f32 tile, not [BH, L, L]."""
    BH, L, D = qh.shape
    scale = D ** -0.5
    nk = L // block_k
    f32 = jnp.float32
    delta = jnp.sum(dout.astype(f32) * out.astype(f32), axis=-1)  # [BH, L]
    q_pos = jax.lax.broadcasted_iota(jnp.int32, (L, block_k), 0)
    k_off = jax.lax.broadcasted_iota(jnp.int32, (L, block_k), 1)

    def per_block(dq, xs):
        j, k_j, v_j = xs                                   # [BH, bk, D]
        s = jnp.einsum("bqd,bkd->bqk", qh, k_j,
                       preferred_element_type=f32) * scale
        if causal:
            s = jnp.where((q_pos >= j * block_k + k_off)[None], s, _NEG_INF)
        p = jnp.exp(s - lse[:, :, None])
        dv_j = jnp.einsum("bqk,bqd->bkd", p.astype(dout.dtype), dout,
                          preferred_element_type=f32)
        dp = jnp.einsum("bqd,bkd->bqk", dout, v_j,
                        preferred_element_type=f32)
        ds = (p * (dp - delta[:, :, None]) * scale).astype(qh.dtype)
        dq = dq + jnp.einsum("bqk,bkd->bqd", ds, k_j,
                             preferred_element_type=f32)
        dk_j = jnp.einsum("bqk,bqd->bkd", ds, qh,
                          preferred_element_type=f32)
        return dq, (dk_j, dv_j)

    def blocks(x):                                         # [nk, BH, bk, D]
        return x.reshape(BH, nk, block_k, D).transpose(1, 0, 2, 3)

    def unblocks(x):
        return x.transpose(1, 0, 2, 3).reshape(BH, L, D)

    dq, (dk, dv) = jax.lax.scan(
        per_block, jnp.zeros((BH, L, D), f32),
        (jnp.arange(nk, dtype=jnp.int32), blocks(kh), blocks(vh)))
    return (dq.astype(qh.dtype), unblocks(dk).astype(kh.dtype),
            unblocks(dv).astype(vh.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(qh, kh, vh, causal, block_q, block_k, interpret):
    return _flash_forward(qh, kh, vh, causal, block_q, block_k,
                          interpret)[0]


def _flash_vjp_fwd(qh, kh, vh, causal, block_q, block_k, interpret):
    out, lse = _flash_forward(qh, kh, vh, causal, block_q, block_k,
                              interpret)
    return out, (qh, kh, vh, out, lse)


def _flash_vjp_bwd(causal, block_q, block_k, interpret, res, dout):
    del block_q, interpret
    return _flash_backward(*res, dout, causal, block_k)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True, block_q: int = 128,
                    block_k: int = 128, interpret: bool = False
                    ) -> jax.Array:
    """q, k, v: [B, L, H, D] -> [B, L, H, D].  L must be a multiple of
    the block sizes (pad upstream).  ``interpret`` runs the kernel in
    the Pallas interpreter (CPU tests)."""
    B, L, H, D = q.shape
    # Collapse batch x heads into the leading grid dimension.
    qh = q.transpose(0, 2, 1, 3).reshape(B * H, L, D)
    kh = k.transpose(0, 2, 1, 3).reshape(B * H, L, D)
    vh = v.transpose(0, 2, 1, 3).reshape(B * H, L, D)
    out = _flash(qh, kh, vh, causal, block_q, block_k, interpret)
    return out.reshape(B, H, L, D).transpose(0, 2, 1, 3)


def attention(q: jax.Array, k: jax.Array, v: jax.Array,
              causal: bool = True) -> jax.Array:
    """Backend dispatch: pallas kernel on TPU, jnp reference elsewhere."""
    from ray_tpu.ops.ring_attention import full_attention
    # Trace-time decision: the backend is fixed per process.
    if (jax.default_backend() == "tpu" and q.shape[1] % 128 == 0
            and q.shape[-1] >= 64):
        return flash_attention(q, k, v, causal=causal)
    return full_attention(q, k, v, causal=causal)
