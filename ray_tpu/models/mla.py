"""Latent attention (MLA, as DeepSeek-V2/V3 publish it): queries and
keys/values pass through low-rank latents, and position enters through
a separate rotary part of the score whose key is ONE head a position,
shared by all query heads.

A layer-pattern kind (``"mla"`` in ``TransformerConfig.layer_pattern``)
with its own parameters under ``lp["mla"]``.  For ``h [B, S, d]`` (the
layer's normed input), ``H`` heads, ranks ``rq``/``rkv``, head sizes
``dn`` (score, without position), ``dr`` (score, rotary), ``dv``:

    c_q        = rmsnorm(h wq_a; q_norm)                 [rq]
    q_nope|q_r = c_q wq_b                                H x (dn | dr)
                 or h wq without a query latent (``q_lora_rank`` None)
    c_kv|k_r   = h wkv_a                                 [rkv | dr]
    k_nope|v   = rmsnorm(c_kv; kv_norm) wkv_b            H x (dn | dv)
    (qk_norm)    q_nope|q_r = rmsnorm(q_nope|q_r; q_head_norm) a head,
                 k_nope = rmsnorm(k_nope; k_head_norm[:dn]) a head,
                 k_r = rmsnorm(k_r; k_head_norm[dn:]), before rotary
    s          = (q_nope . k_nope + rope(q_r) . rope(k_r)) (dn + dr)^-1/2
    out        = softmax_mask(s) v, heads joined, times wo  [H dv, d]

Under ``qk_norm`` a query head is normed over its ``dn + dr`` score
columns; the key's two parts are normed apart, ``k_nope`` a head and the
rotary key once, so that the rotary key stays ONE head a position.

This is the training path: K and V are expanded from the latent and
nothing is absorbed into ``wq_b``/``wo`` (the decode path's trick, with
the latent as the cache).  Both flash kernels take the rotary part as
operands of their own (``ops/flash_attention.py``): the shared key is
never repeated per head, in HBM or in the kernel's reads.

RoPE is over interleaved pairs ``(x[2i], x[2i+1])`` when
``rope_interleave`` (the published layout), else over the two halves.
The rotated pairs leave de-interleaved, ``[all first members ; all
second members]``: a permutation of the rotary columns that q and k
share, which the score does not see.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from ray_tpu.models.common import (LayerCall, LayerKind, RopeTable, _rms_norm,
                                   _rope, stacked_normal)
from ray_tpu.ops.flash_attention import attention


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    #: None: no query latent, the queries are ``h wq``.
    q_lora_rank: Optional[int]
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    rope_interleave: bool = True
    #: RMSNorm over each query head's score columns and over the key's
    #: two parts (weights ``q_head_norm``, ``k_head_norm`` [dn + dr],
    #: shared by the heads) before rotary.
    qk_norm: bool = False


def _init(key: jax.Array, n_layers: int, cfg, options: Dict) -> Dict:
    m, d_model, n_heads = cfg.mla, cfg.d_model, cfg.n_heads
    keys = jax.random.split(jax.random.fold_in(key, 9), 5)
    dn, dr, dv = m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim
    stacked = stacked_normal(n_layers, cfg.dtype)
    if m.q_lora_rank is None:
        queries = {"wq": stacked(keys[1], (d_model, n_heads, dn + dr))}
    else:
        queries = {
            "wq_a": stacked(keys[0], (d_model, m.q_lora_rank)),
            "q_norm": jnp.ones((n_layers, m.q_lora_rank), jnp.float32),
            "wq_b": stacked(keys[1], (m.q_lora_rank, n_heads, dn + dr))}
    if m.qk_norm:
        queries.update({
            name: jnp.ones((n_layers, dn + dr), jnp.float32)
            for name in ("q_head_norm", "k_head_norm")})
    return {"mla": {
        **queries,
        "wkv_a": stacked(keys[2], (d_model, m.kv_lora_rank + dr)),
        "kv_norm": jnp.ones((n_layers, m.kv_lora_rank), jnp.float32),
        "wkv_b": stacked(keys[3], (m.kv_lora_rank, n_heads, dn + dv)),
        "wo": stacked(keys[4], (n_heads, dv, d_model)),
    }}


def _specs(cfg, options: Dict) -> Dict:
    """The up-projections and the output projection by heads over
    ``tp``; the two down-projections and their norms replicated (a
    latent is whole on every shard)."""
    m = cfg.mla
    if m.q_lora_rank is None:
        queries = {"wq": P(None, None, "tp", None)}
    else:
        queries = {"wq_a": P(None, None, None), "q_norm": P(None, None),
                   "wq_b": P(None, None, "tp", None)}
    if m.qk_norm:
        queries.update({"q_head_norm": P(None, None),
                        "k_head_norm": P(None, None)})
    return {"mla": {
        **queries,
        "wkv_a": P(None, None, None),
        "kv_norm": P(None, None),
        "wkv_b": P(None, None, "tp", None),
        "wo": P(None, "tp", None, None),
    }}


def rope(x, positions, theta: float, interleave: bool):
    """x [B, S, H, R] -> the rotated pairs, de-interleaved."""
    if interleave:
        pairs = x.reshape(*x.shape[:-1], x.shape[-1] // 2, 2)
        x = jnp.concatenate([pairs[..., 0], pairs[..., 1]], axis=-1)
    return _rope(x, positions, RopeTable(theta))


def _mla(h, lp: Dict, call: LayerCall):
    """The layer's normed input ``h [B, S, d]`` -> (what attention adds
    to the residual, nothing counted, nothing handed on)."""
    cfg, mesh, positions, lp = call.cfg, call.mesh, call.positions, lp["mla"]
    m, eps = cfg.mla, cfg.norm_eps
    if (cfg.context_parallel and mesh is not None
            and mesh.shape.get("sp", 1) > 1):
        raise ValueError("ring attention has no rotary part: latent "
                         "attention does not run over an sp axis")
    dn, rkv = m.qk_nope_head_dim, m.kv_lora_rank
    # The names: cut points a rematerialised layer may keep
    # (``models/remat.py``) -- the two down-projections, the latents
    # after their norms, the two up-projections whole (their halves go
    # to the kernel: neither half alone spares the product) and the
    # rotary parts as they enter the kernel.
    with jax.named_scope("mla_q"):
        if m.q_lora_rank is None:
            q = checkpoint_name(jnp.einsum("bsd,dhk->bshk", h, lp["wq"]),
                                "mla_q")
        else:
            c_q = checkpoint_name(_rms_norm(
                checkpoint_name(jnp.einsum("bsd,dr->bsr", h, lp["wq_a"]),
                                "mla_q_down"), lp["q_norm"], eps),
                "mla_q_latent")
            q = checkpoint_name(jnp.einsum("bsr,rhk->bshk", c_q, lp["wq_b"]),
                                "mla_q")
        if m.qk_norm:
            q = _rms_norm(q, lp["q_head_norm"], eps)
        q_nope = q[..., :dn]
        q_rope = checkpoint_name(
            rope(q[..., dn:], positions, cfg.rope_theta, m.rope_interleave),
            "mla_q_rope")
    with jax.named_scope("mla_kv"):
        latent = checkpoint_name(jnp.einsum("bsd,dr->bsr", h, lp["wkv_a"]),
                                 "mla_kv_down")
        c_kv = checkpoint_name(_rms_norm(latent[..., :rkv], lp["kv_norm"],
                                         eps), "mla_kv_latent")
        k_r = latent[:, :, None, rkv:]
        if m.qk_norm:
            k_r = _rms_norm(k_r, lp["k_head_norm"][..., dn:], eps)
        k_rope = checkpoint_name(
            rope(k_r, positions, cfg.rope_theta, m.rope_interleave)[:, :, 0],
            "mla_k_rope")
        kv = checkpoint_name(jnp.einsum("bsr,rhk->bshk", c_kv, lp["wkv_b"]),
                             "mla_kv")
        k_nope, v = kv[..., :dn], kv[..., dn:]
        if m.qk_norm:
            k_nope = _rms_norm(k_nope, lp["k_head_norm"][..., :dn], eps)
    o = attention(q_nope, k_nope, v, mask=call.mask, q_rope=q_rope,
                  k_rope=k_rope)
    with jax.named_scope("mla_out"):
        return jnp.einsum("bshk,hkd->bsd", o, lp["wo"]), {}, None


MLA = LayerKind("mla", _init, _specs, _mla, needs="mla")
