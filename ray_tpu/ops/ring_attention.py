"""Ring attention — context parallelism over the ``sp`` mesh axis.

Long-context support the reference never had (SURVEY.md §5.7: "not
present...  the TPU substrate makes ring attention natural").  The sequence
is sharded across devices; each step every device computes a block of
attention between its local queries and the currently-held K/V chunk while
``jax.lax.ppermute`` rotates K/V around the ICI ring — compute and
communication overlap, and no device ever materializes the full [S, S]
score matrix.  Softmax is accumulated flash-style (running max + running
denominator), so the result is exact, not approximate.

Used inside ``shard_map`` with sequence dimension sharded over
``axis_name``.  Causality is handled per (q-chunk, kv-chunk) pair via the
global chunk indices.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from ray_tpu.ops.attention_mask import CAUSAL, FULL


def _block_attn(q, k, v, q_idx, kv_idx, chunk, causal, scale):
    """One q-chunk x kv-chunk block: returns (out_unnorm, row_max, row_sum).

    q: [B, Lq, H, D], k/v: [B, Lk, H, D].
    """
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        q_pos = q_idx * chunk + jax.lax.broadcasted_iota(
            jnp.int32, (logits.shape[-2], logits.shape[-1]), 0)
        k_pos = kv_idx * chunk + jax.lax.broadcasted_iota(
            jnp.int32, (logits.shape[-2], logits.shape[-1]), 1)
        mask = q_pos >= k_pos
        logits = jnp.where(mask[None, None], logits, -jnp.inf)
    m = jnp.max(logits, axis=-1)                      # [B, H, Lq]
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    p = jnp.exp(logits - m_safe[..., None])
    p = jnp.where(jnp.isfinite(logits), p, 0.0)
    l = jnp.sum(p, axis=-1)                           # [B, H, Lq]
    o = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o, m, l


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                   axis_name: str = "sp", causal: bool = True,
                   scale: Optional[float] = None) -> jax.Array:
    """Exact attention with sequence sharded over ``axis_name``.

    Shapes (per device): q, k, v: [B, L_local, H, D].  Must be called
    inside shard_map/pjit with ``axis_name`` a mesh axis; the global
    sequence is the concatenation of the per-device chunks in axis order.
    """
    n = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    chunk = q.shape[1]
    if scale is None:
        scale = q.shape[-1] ** -0.5

    acc = jnp.zeros(q.shape[:3] + (v.shape[-1],), jnp.float32)
    acc = acc.transpose(0, 1, 2, 3)  # [B, Lq, H, D]
    run_max = jnp.full(q.shape[:1] + (q.shape[2], q.shape[1]), -jnp.inf,
                       jnp.float32)  # [B, H, Lq]
    run_sum = jnp.zeros_like(run_max)

    def step(carry, s):
        acc, run_max, run_sum, kk, vv = carry
        kv_idx = (my_idx - s) % n
        o, m, l = _block_attn(q, kk, vv, my_idx, kv_idx, chunk, causal,
                              scale)
        new_max = jnp.maximum(run_max, m)
        # Correction factors; guard fully-masked (-inf) rows.
        corr_old = jnp.exp(jnp.where(jnp.isfinite(run_max),
                                     run_max - new_max, -jnp.inf))
        corr_new = jnp.exp(jnp.where(jnp.isfinite(m), m - new_max, -jnp.inf))
        corr_old = jnp.where(jnp.isfinite(new_max), corr_old, 0.0)
        corr_new = jnp.where(jnp.isfinite(new_max), corr_new, 0.0)
        new_sum = run_sum * corr_old + l * corr_new
        # acc: [B, Lq, H, D]; corr: [B, H, Lq] -> [B, Lq, H, 1]
        acc = acc * corr_old.transpose(0, 2, 1)[..., None] + \
            o * corr_new.transpose(0, 2, 1)[..., None]
        # Rotate K/V to the next device on the ring (overlaps with the
        # next step's compute under XLA's latency-hiding scheduler).
        perm = [(i, (i + 1) % n) for i in range(n)]
        kk = jax.lax.ppermute(kk, axis_name, perm)
        vv = jax.lax.ppermute(vv, axis_name, perm)
        return (acc, new_max, new_sum, kk, vv), None

    (acc, run_max, run_sum, _, _), _ = jax.lax.scan(
        step, (acc, run_max, run_sum, k, v), jnp.arange(n))
    denom = jnp.maximum(run_sum, 1e-20).transpose(0, 2, 1)[..., None]
    return (acc / denom).astype(q.dtype)


def full_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                   mask=CAUSAL,
                   scale: Optional[float] = None,
                   q_rope: Optional[jax.Array] = None,
                   k_rope: Optional[jax.Array] = None) -> jax.Array:
    """Single-device reference attention: q [B, L, H, D], k
    [B, L, H // group, D], v [B, L, H // group, Dv] (query head ``h``
    reads K/V head ``h // group``); ``mask`` is one of
    ``ops.attention_mask``'s descriptions.  ``q_rope`` [B, L, H, R] and
    ``k_rope`` [B, L, R] (one rotary key a position, shared by the
    heads) are further columns of the score, as ``flash_attention``
    takes them."""
    if q_rope is not None:
        q = jnp.concatenate([q, q_rope], axis=-1)
        k = jnp.concatenate([k, jnp.broadcast_to(
            k_rope[:, :, None, :], k.shape[:3] + k_rope.shape[-1:])],
            axis=-1)
    if scale is None:
        scale = q.shape[-1] ** -0.5
    B, lq, H, D = q.shape
    lk, heads_kv = k.shape[1], k.shape[2]
    allowed = None
    if mask != FULL:
        allowed = mask.allowed(
            jax.lax.broadcasted_iota(jnp.int32, (lq, lk), 0),
            jax.lax.broadcasted_iota(jnp.int32, (lq, lk), 1))
    if heads_kv == H:
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                            preferred_element_type=jnp.float32) * scale
        if allowed is not None:
            logits = jnp.where(allowed[None, None], logits, -jnp.inf)
        p = jax.nn.softmax(logits, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                          preferred_element_type=jnp.float32).astype(q.dtype)
    qg = q.reshape(B, lq, heads_kv, H // heads_kv, D)
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k,
                        preferred_element_type=jnp.float32) * scale
    if allowed is not None:
        logits = jnp.where(allowed[None, None, None], logits, -jnp.inf)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, lq, H, v.shape[-1]).astype(q.dtype)
