"""Gated DeltaNet linear attention (as Qwen3-Next publishes it): a
recurrent state a head in place of keys and values, written by the
delta rule and forgotten by a gate, both functions of the input.

A layer-pattern kind (``"gdn"`` in ``TransformerConfig.layer_pattern``)
with its own parameters under ``lp["gdn"]``.  For ``h [B, S, d]`` (the
layer's normed input), ``Hk`` key heads of ``Dk``, ``Hv = r Hk`` value
heads of ``Dv`` (key head ``j`` serves value heads ``r j .. r j + r - 1``):

    q | k | v | z = h w_qkvz       a key head's columns together:
                                   [Dk | Dk | r Dv | r Dv]
    b | a         = h w_ba         [r | r] a key head
    q | k | v     = silu(conv(q | k | v))   causal, depthwise, ``taps``
                    positions, zeros before the row's start, no bias
                    (ops/causal_conv.py)
    q, k          = l2norm(q) Dk^-1/2, l2norm(k)      (eps 1e-6)
    beta          = sigmoid(b)                         float32
    g             = -exp(A_log) softplus(a + dt_bias)  float32
    o             = gated_delta_rule(q, k, v, g, beta) (ops/gated_delta.py;
                    q, k with their Hk heads, v, g, beta with Hv)
    out           = (rmsnorm(o; norm) silu(z), heads joined) wo

The fused projections are laid out a key head at a time so that ``tp``
shards value heads with the key head they read and their q/k/z/b/a
columns, conv taps, ``A_log`` and ``dt_bias`` with them, and ``wo`` by
rows.  ``norm`` [Dv] is a plain RMSNorm weight (1 at the start), shared
by the heads.  A row is one causal sequence: the state and the
convolution cross whatever separators it holds, as attention does.  Over
an ``sp`` axis the layer raises (the state would have to pass from shard
to shard).

What the rule leaves in HBM on a TPU: its operands as this layer makes
them (q, k at ``Hk`` heads, v, g, beta), ``o`` and, between the forward
rule and the backward one, the state entering every eighth chunk in
float32 (those two kept by a rematerialised layer whatever its plan); no
copy of q and k a value head, no ``[C, C]`` tensor, no ``W``, ``U`` or
``V'``, no state a chunk (``ops/gated_delta.py``).  What the convolution
reads and writes there: ``qkvz`` as the projection left it (the ``q |
k | v`` columns of every key head through the kernels' block index: no
sliced copy, no padded float32 copy, no sum before SiLU as an array) and
``mixed`` in float32, once each; backward ``qkvz``, ``mixed``'s cotangent and
``qkvz``'s (zeros in ``z``'s columns), once each, and the taps' gradient
as a sum a tile (``ops/causal_conv.py``).  Off the TPU, or at channels
that are not whole 128-lane blocks, the plain ``causal_conv`` and XLA's
SiLU run instead and ``gdn_conv_fallback_passes`` counts 1.
"""

from __future__ import annotations

import dataclasses
from typing import Dict

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from ray_tpu.models.common import LayerCall, LayerKind, stacked_normal
from ray_tpu.ops import causal_conv as conv_op
from ray_tpu.ops.attention_mask import CAUSAL
# (the tests take the plain filter from here)
from ray_tpu.ops.causal_conv import causal_conv  # noqa: F401

_L2_EPS = 1e-6


@dataclasses.dataclass(frozen=True)
class GDNConfig:
    num_key_heads: int
    num_value_heads: int
    key_head_dim: int
    value_head_dim: int
    conv_kernel: int = 4
    chunk: int = 64

    @property
    def ratio(self) -> int:
        return self.num_value_heads // self.num_key_heads


def init_gdn_params(rng: jax.Array, n_layers: int, d_model: int,
                    g: GDNConfig, dtype) -> Dict:
    """Matrices and taps N(0, 0.02); ``A_log = log U(0, 16)``,
    ``dt_bias`` and ``norm`` 1, as the published modelling code."""
    keys = jax.random.split(rng, 5)
    hk, r, dk, dv = g.num_key_heads, g.ratio, g.key_head_dim, g.value_head_dim
    stacked = stacked_normal(n_layers, dtype)
    return {
        "w_qkvz": stacked(keys[0], (d_model, hk, 2 * dk + 2 * r * dv)),
        "w_ba": stacked(keys[1], (d_model, hk, 2 * r)),
        "conv": stacked(keys[2], (hk, 2 * dk + r * dv, g.conv_kernel)),
        "A_log": jnp.log(jax.random.uniform(
            keys[3], (n_layers, hk, r), jnp.float32, 1e-6, 16.0)),
        "dt_bias": jnp.ones((n_layers, hk, r), jnp.float32),
        "norm": jnp.ones((n_layers, dv), jnp.float32),
        "wo": stacked(keys[4], (hk, r * dv, d_model)),
    }


def _specs(cfg, options: Dict) -> Dict:
    """Everything that has a key head axis over ``tp`` by it; the output
    norm replicated."""
    return {"gdn": {
        "w_qkvz": P(None, None, "tp", None),
        "w_ba": P(None, None, "tp", None),
        "conv": P(None, "tp", None, None),
        "A_log": P(None, "tp", None),
        "dt_bias": P(None, "tp", None),
        "norm": P(None, None),
        "wo": P(None, "tp", None, None),
    }}


def _l2norm(x):
    xf = x.astype(jnp.float32)
    return xf * jax.lax.rsqrt(jnp.sum(xf * xf, axis=-1, keepdims=True)
                              + _L2_EPS)


def gdn_attention(h, lp: Dict, cfg, mesh=None):
    """The layer's normed input ``h [B, S, d]`` -> (what the delta layer
    adds to the residual, what it counted: ``gdn_state_norm`` -- the
    root mean square of the state entering a row's last chunk --
    ``gdn_decay_mean``, the mean of ``exp(g)``, ``gdn_beta_mean`` and
    ``gdn_conv_fallback_passes``: 1 where the convolution ran as the
    ``jnp`` form, 0 where as the kernels).
    ``lp``: this layer's ``gdn`` parameters."""
    from ray_tpu.ops.gated_delta import gated_delta_rule
    g_ = cfg.gdn
    if (cfg.context_parallel and mesh is not None
            and mesh.shape.get("sp", 1) > 1):
        raise ValueError("a delta layer's state passes along the row: it "
                         "does not run over an sp axis")
    hk, r, dk, dv = (g_.num_key_heads, g_.ratio, g_.key_head_dim,
                     g_.value_head_dim)
    b, s, _ = h.shape
    f32 = jnp.float32
    # The names: cut points a rematerialised layer may keep
    # (``models/remat.py``) -- the two projections and the convolution's
    # output after SiLU (the kernels leave no sum before it in HBM).
    with jax.named_scope("gdn_proj"):
        qkvz = checkpoint_name(
            jnp.einsum("bsd,dhc->bshc", h, lp["w_qkvz"]), "gdn_qkvz")
        ba = checkpoint_name(
            jnp.einsum("bsd,dhc->bshc", h, lp["w_ba"]), "gdn_ba").astype(f32)
        z = qkvz[..., 2 * dk + r * dv:].reshape(b, s, hk * r, dv)
    with jax.named_scope("gdn_conv"):
        # q | k | v of every key head, read out of qkvz by the kernels'
        # block index: z's columns are never sliced away in HBM
        mixed = checkpoint_name(
            conv_op.causal_conv_silu(qkvz, lp["conv"]), "gdn_mixed")
    with jax.named_scope("gdn_core"):
        q = (_l2norm(mixed[..., :dk]) * dk ** -0.5).astype(h.dtype)
        k = _l2norm(mixed[..., dk:2 * dk]).astype(h.dtype)
        v = mixed[..., 2 * dk:].astype(h.dtype).reshape(b, s, hk * r, dv)
        beta = jax.nn.sigmoid(ba[..., :r]).reshape(b, s, hk * r)
        g = (-jnp.exp(lp["A_log"]) * jax.nn.softplus(
            ba[..., r:] + lp["dt_bias"])).reshape(b, s, hk * r)
        # q and k go in with their key heads: the kernels fetch a key
        # head's block for each value head that reads it, nothing is
        # repeated in HBM and dq, dk return summed a key head
        o, state = gated_delta_rule(q, k, v, g, beta,
                                    chunk=min(g_.chunk, s), with_state=True)
        counted = {
            "gdn_state_norm": jnp.sqrt(jnp.mean(jnp.square(state))),
            "gdn_decay_mean": jax.lax.stop_gradient(jnp.mean(jnp.exp(g))),
            "gdn_beta_mean": jax.lax.stop_gradient(jnp.mean(beta)),
            "gdn_conv_fallback_passes": jnp.asarray(
                conv_op.fallback_passes(qkvz.shape, lp["conv"].shape), f32),
        }
    with jax.named_scope("gdn_out"):
        o = o.astype(f32)
        o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                              + cfg.norm_eps) * lp["norm"]
        o = (o * jax.nn.silu(z.astype(f32))).astype(h.dtype)
        out = jnp.einsum("bshk,hkd->bsd", o.reshape(b, s, hk, r * dv),
                         lp["wo"])
    return out, counted


def _init(key: jax.Array, n_layers: int, cfg, options: Dict) -> Dict:
    return {"gdn": init_gdn_params(jax.random.fold_in(key, 10), n_layers,
                                   cfg.d_model, cfg.gdn, cfg.dtype)}


def _gdn(h, lp: Dict, call: LayerCall):
    if call.mask != CAUSAL:
        raise ValueError("a delta layer is causal")
    return (*gdn_attention(h, lp["gdn"], call.cfg, call.mesh), None)


GDN = LayerKind("gdn", _init, _specs, _gdn, needs="gdn")
