"""Mamba-1 selective state-space mixer, and the Gated Memory Unit that
reads an earlier Mamba layer's scan output in the place of a scan of its
own (a decoder-hybrid-decoder's second half).

Two layer-pattern kinds, each with its parameters under ``lp[kind]``.
For ``u [B, S, d]`` (the layer's normed input), ``E`` inner channels,
``N`` states a channel, ``R`` the rank of the step size:

``"mamba"``

    s | z         = u w_in                     [E | E], no bias
    c             = silu(conv(s) + conv_b)     causal, depthwise, ``taps``
                                               positions, zeros before
                                               the row's start
    dt | B | C    = c w_x                      [R | N | N], no bias
    delta         = softplus(dt w_dt + dt_b)   float32, [E]
    A             = -exp(A_log)                float32, [E, N]
    y             = selective_scan(c, delta, A, B, C, D)
                                               (ops/selective_scan.py:
                                               the D skip included)
    out           = (y silu(z)) w_out          no bias

A layer whose run says ``writes=memory`` also hands ``y`` -- with the
``D`` skip, before the gate -- to the layers after it.

``"gmu"`` (reads that memory ``M``):

    out           = (M silu(u w_in)) w_out     w_in [d, E], w_out [E, d]

A row is one causal sequence: the state and the convolution cross
whatever separators it holds.  Neither kind has a ``tp`` or ``sp``
layout yet (the state would have to pass from shard to shard):
the specs replicate, the kinds refuse such a mesh
(``common.on_one_device``) and the pipeline schedule refuses them.
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
from ray_tpu.ops import selective_scan as scan_op
from ray_tpu.ops.causal_conv import causal_conv


@dataclasses.dataclass(frozen=True)
class MambaConfig:
    d_inner: int
    d_state: int = 16
    d_conv: int = 4
    dt_rank: int = 0          # 0: ceil(d_model / 16), set by the model
    chunk: int = scan_op.CHUNK
    #: ``dt_b`` starts as the inverse softplus of a step size drawn
    #: log-uniformly between these two.
    dt_min: float = 1e-3
    dt_max: float = 1e-1


def rank_of(m: MambaConfig, d_model: int) -> int:
    return m.dt_rank or -(-d_model // 16)


def _init_mamba(key: jax.Array, n_layers: int, cfg, options: Dict) -> Dict:
    """Matrices and taps N(0, 0.02), ``w_dt`` U(+-R^-1/2); ``A_log =
    log(1 .. N)`` a channel, ``D`` 1, the convolution's bias 0, ``dt_b``
    the inverse softplus of ``exp(U(log dt_min, log dt_max))``: the
    published modelling code's."""
    m, d_model, dtype = cfg.mamba, cfg.d_model, cfg.dtype
    keys = jax.random.split(jax.random.fold_in(key, 11), 6)
    e, n, r = m.d_inner, m.d_state, rank_of(m, d_model)
    f32, stacked = jnp.float32, stacked_normal(n_layers, dtype)
    step = jnp.exp(jax.random.uniform(
        keys[4], (n_layers, e), f32, math.log(m.dt_min), math.log(m.dt_max)))
    return {"mamba": {
        "w_in": stacked(keys[0], (d_model, 2 * e)),
        "conv": stacked(keys[1], (e, m.d_conv)),
        "conv_b": jnp.zeros((n_layers, e), f32),
        "w_x": stacked(keys[2], (e, r + 2 * n)),
        "w_dt": jax.random.uniform(keys[3], (n_layers, r, e), f32,
                                   -r ** -0.5, r ** -0.5).astype(dtype),
        "dt_b": step + jnp.log(-jnp.expm1(-step)),
        "A_log": jnp.broadcast_to(jnp.log(jnp.arange(1, n + 1, dtype=f32)),
                                  (n_layers, e, n)),
        "D": jnp.ones((n_layers, e), f32),
        "w_out": stacked(keys[5], (e, d_model)),
    }}


def _init_gmu(key: jax.Array, n_layers: int, cfg, options: Dict) -> Dict:
    k_in, k_out = jax.random.split(jax.random.fold_in(key, 12))
    d_model, e = cfg.d_model, cfg.mamba.d_inner
    stacked = stacked_normal(n_layers, cfg.dtype)
    return {"gmu": {"w_in": stacked(k_in, (d_model, e)),
                    "w_out": stacked(k_out, (e, d_model))}}


def _mamba(h, lp: Dict, call: LayerCall):
    """The layer's normed input ``h [B, S, d]`` -> (what the Mamba layer
    adds to the residual, what it counted -- ``ssm_scan_fallback_passes``:
    1 where the scan ran as the ``jnp`` scans, ``ssm_delta_mean``: the
    mean step size -- and, where the run writes the memory, the scan's
    output ``y [B, S, E]`` before the gate)."""
    on_one_device(call)
    cfg, lp = call.cfg, lp["mamba"]
    m = cfg.mamba
    e, n, r = m.d_inner, m.d_state, rank_of(m, cfg.d_model)
    f32 = jnp.float32
    # The names: cut points a rematerialised layer may keep
    # (``models/remat.py``) -- the input projection, the convolution's
    # output and the small projection.  The scan's output carries none:
    # its backward kernel reads the entering states, which only the
    # forward kernel makes, so a kept ``y`` would spare nothing
    # (``ops/selective_scan.py``).
    with jax.named_scope("ssm_proj"):
        sz = checkpoint_name(jnp.einsum("bsd,de->bse", h, lp["w_in"]),
                             "ssm_in")
        z = sz[..., e:]
    with jax.named_scope("ssm_conv"):
        c = checkpoint_name(jax.nn.silu(
            causal_conv(sz[..., :e], lp["conv"]) + lp["conv_b"]
        ).astype(h.dtype), "ssm_conv")
    with jax.named_scope("ssm_proj"):
        dbc = checkpoint_name(jnp.einsum("bse,er->bsr", c, lp["w_x"]),
                              "ssm_dbc")
        delta = jax.nn.softplus(
            jnp.einsum("bsr,re->bse", dbc[..., :r], lp["w_dt"],
                       preferred_element_type=f32) + lp["dt_b"])
    with jax.named_scope("ssm_scan"):
        y = scan_op.selective_scan(
            c, delta, -jnp.exp(lp["A_log"]), dbc[..., r:r + n].astype(f32),
            dbc[..., r + n:].astype(f32), lp["D"],
            chunk=min(m.chunk, h.shape[1]))
        counted = {
            "ssm_scan_fallback_passes": jnp.asarray(
                scan_op.fallback_passes(e), f32),
            "ssm_delta_mean": jax.lax.stop_gradient(jnp.mean(delta)),
        }
    with jax.named_scope("ssm_out"):
        gated = (y.astype(f32) * jax.nn.silu(z.astype(f32))).astype(h.dtype)
        out = jnp.einsum("bse,ed->bsd", gated, lp["w_out"])
    return out, counted, y if "writes" in call.options else None


def _gmu(h, lp: Dict, call: LayerCall):
    """The layer's normed input -> what the Gated Memory Unit adds to
    the residual: the memory ``[B, S, E]`` it reads, gated."""
    on_one_device(call)
    lp, memory, f32 = lp["gmu"], call.shared["memory"], jnp.float32
    with jax.named_scope("gmu"):
        gate = checkpoint_name(jnp.einsum("bsd,de->bse", h, lp["w_in"]),
                               "gmu_gate")
        gated = (memory.astype(f32)
                 * jax.nn.silu(gate.astype(f32))).astype(h.dtype)
        return jnp.einsum("bse,ed->bsd", gated, lp["w_out"]), {}, None


MAMBA = LayerKind("mamba", _init_mamba, replicated(_init_mamba), _mamba,
                  options={"writes": ("memory",)}, needs="mamba",
                  single_device=True)
GMU = LayerKind("gmu", _init_gmu, replicated(_init_gmu), _gmu,
                implied={"reads": "memory"}, needs="mamba", single_device=True)
