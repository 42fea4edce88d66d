"""Mamba-2 mixer (as Nemotron-H publishes it): a state-space layer whose
state decays by one scalar a head and position, with ``B`` and ``C``
shared by groups of heads, run in chunks (``ops/ssd.py``).

A layer-pattern kind (``"mamba2"``) with its parameters under
``lp["mamba2"]``.  For ``h [B, S, d]`` (the layer's normed input), ``H``
heads of ``P`` channels, ``G`` groups of ``N`` states:

    z             = h w_z                       [H P]
    x | B | C     = silu(conv(h w_xbc) + conv_b)
                                               [H P | G N | G N], causal,
                                               depthwise, ``taps``
                                               positions, zeros before
                                               the row's start
                                               (ops/causal_conv.py)
    dt            = softplus(h w_dt + dt_bias)  float32, [H]
    A             = -exp(A_log)                 float32, [H]
    y             = ssd_rule(x, dt, A, B, C) + D x
                                               (head ``j`` reads group
                                               ``j // (H / G)``)
    out           = rmsnorm_groups(y silu(z); norm) w_out
                                               the norm AFTER the gate,
                                               over ``norm_groups`` groups
                                               of the ``H P`` channels

The published fused input projection ``[z | xBC | dt]`` is three
matrices here (the same parameters): the convolution's kernels read
``x | B | C`` whole, and nothing is sliced out of a wider product.  The
convolution's output, ``dt`` and the rule's ``y`` are held positions-minor
(``[B, channels, S]``), as the kernels lay them out: the rule's kernels
read ``x``, ``B`` and ``C`` out of that one array (``ssd.ssd_mixed``).  A row
is one causal sequence: the state and the convolution cross whatever
separators it holds.  The kind has no ``tp`` or ``sp`` layout yet (the
state would pass from shard to shard): the specs replicate and the kind
refuses such a mesh (``common.on_one_device``).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.models.common import (LayerCall, LayerKind, on_one_device,
                                   replicated, stacked_normal)
from ray_tpu.ops import causal_conv as conv_op
from ray_tpu.ops import ssd as ssd_op


@dataclasses.dataclass(frozen=True)
class Mamba2Config:
    num_heads: int
    head_dim: int
    n_groups: int
    state_size: int
    conv_kernel: int = 4
    chunk: int = ssd_op.CHUNK
    #: Groups of the ``H P`` channels the gated output norm runs over.
    norm_groups: int = 1
    #: ``dt_bias`` starts as the inverse softplus of a step size drawn
    #: log-uniformly between these two, floored at ``dt_floor``.
    dt_min: float = 1e-3
    dt_max: float = 1e-1
    dt_floor: float = 1e-4

    @property
    def inner(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def conv_dim(self) -> int:
        return self.inner + 2 * self.n_groups * self.state_size


def _init(key: jax.Array, n_layers: int, cfg, options: Dict) -> Dict:
    """Matrices N(0, 0.02); taps and their bias U(+-K^-1/2) (a depthwise
    ``Conv1d``'s own); ``A_log = log U(1, 16)``, ``D`` and ``norm`` 1,
    ``dt_bias`` the inverse softplus of ``exp(U(log dt_min, log
    dt_max))`` floored: the published modelling code's."""
    m, d, dtype = cfg.mamba2, cfg.d_model, cfg.dtype
    keys = jax.random.split(jax.random.fold_in(key, 13), 7)
    f32, stacked = jnp.float32, stacked_normal(n_layers, dtype)
    bound = m.conv_kernel ** -0.5
    step = jnp.maximum(jnp.exp(jax.random.uniform(
        keys[5], (n_layers, m.num_heads), f32, math.log(m.dt_min),
        math.log(m.dt_max))), m.dt_floor)
    return {"mamba2": {
        "w_z": stacked(keys[0], (d, m.inner)),
        "w_xbc": stacked(keys[1], (d, m.conv_dim)),
        "w_dt": stacked(keys[2], (d, m.num_heads)),
        "conv": jax.random.uniform(keys[3], (n_layers, m.conv_dim,
                                             m.conv_kernel), f32, -bound,
                                   bound).astype(dtype),
        "conv_b": jax.random.uniform(keys[4], (n_layers, m.conv_dim), f32,
                                     -bound, bound),
        "dt_bias": step + jnp.log(-jnp.expm1(-step)),
        "A_log": jnp.log(jax.random.uniform(keys[6], (n_layers, m.num_heads),
                                            f32, 1.0, 16.0)),
        "D": jnp.ones((n_layers, m.num_heads), f32),
        "norm": jnp.ones((n_layers, m.inner), f32),
        "w_out": stacked(jax.random.fold_in(key, 14), (m.inner, d)),
    }}


def gated_group_norm(y, z, weight, groups: int, eps: float):
    """``y silu(z)`` over ``groups`` equal groups of the last axis, each
    scaled to unit root mean square, times ``weight``: float32."""
    f32 = jnp.float32
    gated = y.astype(f32) * jax.nn.silu(z.astype(f32))
    parts = gated.reshape(*gated.shape[:-1], groups, -1)
    parts = parts * jax.lax.rsqrt(
        jnp.mean(parts * parts, axis=-1, keepdims=True) + eps)
    return parts.reshape(gated.shape) * weight


def _mamba2(h, lp: Dict, call: LayerCall):
    """The layer's normed input ``h [B, S, d]`` -> (what the mixer adds
    to the residual, what it counted -- ``ssd_fallback_passes``: 1 where
    the rule or the convolution ran as ``jnp``, 0 where both ran as
    their kernels; ``ssd_dt_mean``: the mean step size -- None)."""
    on_one_device(call)
    cfg, lp = call.cfg, lp["mamba2"]
    m = cfg.mamba2
    b, s, _ = h.shape
    f32 = jnp.float32
    hp = m.inner
    # The names: cut points a rematerialised layer may keep
    # (``models/remat.py``): the three projections and the
    # convolution's output.  The rule's output and its step states are
    # the kernels' own (``ssd.RESIDUAL_NAMES``: kept always).
    with jax.named_scope("ssd_proj"):
        z = checkpoint_name(jnp.einsum("bsd,de->bse", h, lp["w_z"]), "ssd_z")
        xbc = checkpoint_name(jnp.einsum("bsd,de->bse", h, lp["w_xbc"]),
                              "ssd_xbc")
        # positions minor, [B, H, S], as the rule reads the step sizes
        dt = checkpoint_name(jnp.einsum("bsd,dh->bhs", h, lp["w_dt"],
                                        preferred_element_type=f32), "ssd_dt")
    with jax.named_scope("ssd_conv"):
        # positions minor, [B, conv_dim, S]: as the convolution's kernels
        # write it and the rule's read it
        mixed = checkpoint_name(jnp.swapaxes(conv_op.causal_conv_silu(
            xbc, lp["conv"], lp["conv_b"]), 1, 2).astype(h.dtype), "ssd_conv")
    with jax.named_scope("ssd_rule"):
        # the rule reads x, B and C out of ``mixed`` itself and writes y
        # [B, H, P, S]; the D skip is XLA's, fused into the norm's first
        # pass
        delta = jax.nn.softplus(dt + lp["dt_bias"][:, None])
        y = ssd_op.ssd_mixed(mixed, delta, -jnp.exp(lp["A_log"]), m.n_groups,
                             m.state_size, chunk=min(m.chunk, s))
        x = mixed[:, :hp].reshape(y.shape)
        y = (y.astype(f32) + lp["D"][:, None, None] * x.astype(f32)
             ).reshape(b, hp, s)
        counted = {
            "ssd_fallback_passes": jnp.asarray(max(
                ssd_op.fallback_passes(),
                conv_op.fallback_passes(xbc.shape, lp["conv"].shape)), f32),
            "ssd_dt_mean": jax.lax.stop_gradient(jnp.mean(delta)),
        }
    with jax.named_scope("ssd_norm"):
        normed = gated_group_norm(jnp.swapaxes(y, 1, 2), z, lp["norm"],
                                  m.norm_groups, cfg.norm_eps)
    with jax.named_scope("ssd_out"):
        out = jnp.einsum("bse,ed->bsd", normed.astype(h.dtype), lp["w_out"])
    return out, counted, None


MAMBA2 = LayerKind("mamba2", _init, replicated(_init), _mamba2,
                   needs="mamba2", single_device=True)
