"""Kimi Delta Attention (KDA, as Kimi Linear and Ling-3.0 publish it):
the gated delta rule whose state forgets by a rate a key channel, not a
head, with short convolutions before it and a gate a head after it.

A layer-pattern kind (``"kda"`` in ``TransformerConfig.layer_pattern``)
with its own parameters under ``lp["kda"]``.  For ``h [B, S, d]`` (the
layer's normed input), ``H`` heads of ``Dk = Dv`` channels:

    q | k | v = h w_qkv                  a head's columns together:
                                          [Dk | Dk | Dv]
    q | k | v = silu(conv(q | k | v))     causal, depthwise, ``taps``
                                          positions, zeros before the
                                          row's start, no bias
                                          (ops/causal_conv.py)
    q, k      = l2norm(q) Dk^-1/2, l2norm(k)        (eps 1e-6)
    g         = LOWER sigmoid(exp(A_log) (h w_alpha + dt_bias))
                                          float32 [H, Dk], in (LOWER, 0):
                                          the bounded (``safe``) gate
    beta      = sigmoid(h w_beta)         float32 [H]
    o         = kda_rule(q, k, v, g, beta)          (ops/kda.py)
    out       = (rmsnorm(o; norm) sigmoid(h w_gate), heads joined) wo
                                          one gate a head, one norm
                                          weight [Dv] the heads share

``A_log`` is a head's, ``dt_bias`` a channel's.  Every leaf with a head
axis is laid out by heads, so ``tp`` shards heads with their columns,
taps, rates and gates, and ``wo`` by rows; ``norm`` is replicated.  A row
is one causal sequence: the state and the convolution cross whatever
separators it holds.  Over an ``sp`` axis the layer raises (the state
would have to pass from shard to shard).

On a TPU the rule reads q, k, v and ``g`` where this layer leaves them
(``[B, S, H D]``), forms the running sum of ``g`` itself and writes
``o`` there, and the convolution reads ``qkv`` as the projection left it;
off the TPU, or at channels that are not whole 128-lane blocks, the
``jnp`` forms run and ``kda_fallback_passes`` counts 1.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from ray_tpu.models.common import LayerCall, LayerKind, stacked_normal
from ray_tpu.ops import causal_conv as conv_op
from ray_tpu.ops import kda as kda_op
from ray_tpu.ops.attention_mask import CAUSAL

_L2_EPS = 1e-6
#: ``dt_bias`` starts as the inverse softplus of a step size drawn
#: log-uniformly between these two.
_DT_RANGE = (1e-3, 1e-1)


@dataclasses.dataclass(frozen=True)
class KDAConfig:
    num_heads: int
    head_dim: int
    conv_kernel: int = 4
    chunk: int = kda_op.CHUNK
    #: The gate's lower bound: every log-decay lies in (lower, 0).
    lower: float = kda_op.LOWER


def _init(key: jax.Array, n_layers: int, cfg, options: Dict) -> Dict:
    """Matrices N(0, 0.02); taps U(+-K^-1/2) (a depthwise ``Conv1d``'s
    own); ``A_log = log U(1, 16)``; ``dt_bias`` the inverse softplus of
    ``exp(U(log 1e-3, log 1e-1))``; ``norm`` 1."""
    m, d, dtype = cfg.kda, cfg.d_model, cfg.dtype
    h, dk = m.num_heads, m.head_dim
    keys = jax.random.split(jax.random.fold_in(key, 15), 8)
    f32, stacked = jnp.float32, stacked_normal(n_layers, dtype)
    bound = m.conv_kernel ** -0.5
    step = jnp.exp(jax.random.uniform(keys[6], (n_layers, h, dk), f32,
                                      *map(math.log, _DT_RANGE)))
    return {"kda": {
        "w_qkv": stacked(keys[0], (d, h, 3 * dk)),
        "conv": jax.random.uniform(keys[1], (n_layers, h, 3 * dk,
                                             m.conv_kernel), f32, -bound,
                                   bound).astype(dtype),
        "w_alpha": stacked(keys[2], (d, h, dk)),
        "A_log": jnp.log(jax.random.uniform(keys[3], (n_layers, h), f32,
                                            1.0, 16.0)),
        "dt_bias": step + jnp.log(-jnp.expm1(-step)),
        "w_beta": stacked(keys[4], (d, h)),
        "w_gate": stacked(keys[5], (d, h)),
        "norm": jnp.ones((n_layers, dk), f32),
        "wo": stacked(keys[7], (h, dk, d)),
    }}


def _specs(cfg, options: Dict) -> Dict:
    """Everything that has a head axis over ``tp`` by it; the output
    norm replicated."""
    return {"kda": {
        "w_qkv": P(None, None, "tp", None),
        "conv": P(None, "tp", None, None),
        "w_alpha": P(None, None, "tp", None),
        "A_log": P(None, "tp"),
        "dt_bias": P(None, "tp", None),
        "w_beta": P(None, None, "tp"),
        "w_gate": P(None, None, "tp"),
        "norm": P(None, None),
        "wo": P(None, "tp", None, None),
    }}


def _l2norm(x):
    xf = x.astype(jnp.float32)
    return xf * jax.lax.rsqrt(jnp.sum(xf * xf, axis=-1, keepdims=True)
                              + _L2_EPS)


def kda_attention(h, lp: Dict, cfg, mesh=None):
    """The layer's normed input ``h [B, S, d]`` -> (what the layer adds
    to the residual, what it counted: ``kda_fallback_passes`` -- 1 where
    the rule or the convolution ran as ``jnp``, 0 where both ran as
    their kernels -- and ``kda_decay_mean``, the mean of ``exp(g)``).
    ``lp``: this layer's ``kda`` parameters."""
    m = cfg.kda
    if (cfg.context_parallel and mesh is not None
            and mesh.shape.get("sp", 1) > 1):
        raise ValueError("a delta layer's state passes along the row: it "
                         "does not run over an sp axis")
    dk = m.head_dim
    f32 = jnp.float32
    # The names: cut points a rematerialised layer may keep
    # (``models/remat.py``) -- the projections and the convolution's
    # output.  The rule's output and its step states are the kernels'
    # own (``kda.RESIDUAL_NAMES``: kept always).
    with jax.named_scope("kda_proj"):
        qkv = checkpoint_name(jnp.einsum("bsd,dhc->bshc", h, lp["w_qkv"]),
                              "kda_qkv")
        alpha = checkpoint_name(jnp.einsum(
            "bsd,dhc->bshc", h, lp["w_alpha"], preferred_element_type=f32),
            "kda_alpha")
        beta_gate = checkpoint_name(jnp.einsum(
            "bsd,dgh->bshg", h, jnp.stack([lp["w_beta"], lp["w_gate"]], -2),
            preferred_element_type=f32), "kda_beta_gate")
    with jax.named_scope("kda_conv"):
        mixed = checkpoint_name(conv_op.causal_conv_silu(qkv, lp["conv"]),
                                "kda_mixed")
    with jax.named_scope("kda_core"):
        q = (_l2norm(mixed[..., :dk]) * dk ** -0.5).astype(h.dtype)
        k = _l2norm(mixed[..., dk:2 * dk]).astype(h.dtype)
        v = mixed[..., 2 * dk:].astype(h.dtype)
        g = m.lower * jax.nn.sigmoid(
            jnp.exp(lp["A_log"])[:, None] * (alpha + lp["dt_bias"]))
        beta = jax.nn.sigmoid(beta_gate[..., 0])
        o = kda_op.kda_rule(q, k, v, g, beta, chunk=min(m.chunk, h.shape[1]))
        counted = {
            "kda_fallback_passes": jnp.asarray(max(
                kda_op.fallback_passes(),
                conv_op.fallback_passes(qkv.shape, lp["conv"].shape)), f32),
            "kda_decay_mean": jax.lax.stop_gradient(jnp.mean(jnp.exp(g))),
        }
    with jax.named_scope("kda_out"):
        o = o.astype(f32)
        o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                              + cfg.norm_eps) * lp["norm"]
        o = (o * jax.nn.sigmoid(beta_gate[..., 1:])).astype(h.dtype)
        out = jnp.einsum("bshk,hkd->bsd", o, lp["wo"])
    return out, counted


def _kda(h, lp: Dict, call: LayerCall):
    if call.mask != CAUSAL:
        raise ValueError("a delta layer is causal")
    return (*kda_attention(h, lp["kda"], call.cfg, call.mesh), None)


KDA = LayerKind("kda", _init, _specs, _kda, needs="kda")
