"""Rotary multi-head / grouped attention: the ``"mha"`` kind.

Its leaves lie flat in the layer's tree (``wq``, ``wk``, ``wv``, ``wo``,
``wg`` under the head-wise gate, ``q_norm``/``k_norm`` under
``qk_norm``), heads over ``tp``.  A run may say ``heads=``, ``window=``
and ``rope=`` after the kind (``mha_run``); one that says nothing is
``cfg.n_heads`` causal heads under ``cfg.rope_theta`` /
``cfg.rotary_dim``.  Over an ``sp`` axis the run is ring attention
(``ops/ring_attention.py``): causal and multi-head only.
"""

from __future__ import annotations

import contextlib
import functools
from typing import Dict

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from ray_tpu.models.common import (LayerCall, LayerKind, RopeTable, _rms_norm,
                                   _rope, norm_start, norm_weight,
                                   stacked_normal)
from ray_tpu.ops.attention_mask import CAUSAL, SlidingWindow
from ray_tpu.ops.flash_attention import attention as flash_or_ref_attention
from ray_tpu.ops.ring_attention import ring_attention


def mha_run(cfg, run: str, options: Dict):
    """What an ``mha`` run's layers are -> (query heads, the mask they
    bring or None for the caller's, the rotary table).  A run whose
    heads the K/V heads do not divide, or that names a table the
    configuration lacks, is refused by its name."""
    heads = options.get("heads", cfg.n_heads)
    if heads < 1 or heads % cfg.n_kv_heads:
        raise ValueError(f"{run!r}: {heads} query heads over "
                         f"{cfg.n_kv_heads} K/V heads")
    tables = dict(cfg.rope_tables)
    if "rope" not in options:
        table = RopeTable(cfg.rope_theta, cfg.rotary_dim)
    elif options["rope"] in tables:
        table = tables[options["rope"]]
    else:
        raise ValueError(f"{run!r}: the configuration's rotary "
                         f"tables are {sorted(tables)}")
    mask = SlidingWindow(options["window"]) if "window" in options else None
    return heads, mask, table


def _check(call: LayerCall) -> None:
    """A run's heads split over ``tp`` by the K/V heads (which divide
    every run's query heads, so a ``tp`` that takes them takes the run);
    the ring over ``sp`` is causal and multi-head only."""
    cfg = call.cfg
    heads, mask, _ = mha_run(cfg, call.run, call.options)
    if call.mesh is None:
        return
    tp, sp = call.mesh.shape.get("tp", 1), call.mesh.shape.get("sp", 1)
    if cfg.n_kv_heads % tp:
        raise ValueError(f"{cfg.n_kv_heads} K/V heads on tp={tp}")
    if sp > 1 and cfg.context_parallel and (
            mask is not None or heads != cfg.n_kv_heads):
        raise ValueError(f"{call.run!r} on sp={sp}: ring attention "
                         f"is causal and multi-head only")


def _init(key: jax.Array, n_layers: int, cfg, options: Dict) -> Dict:
    d, dh, kv = cfg.d_model, cfg.head_dim, cfg.n_kv_heads
    h = options.get("heads", cfg.n_heads)
    lkeys = jax.random.split(key, 6)     # (``common.DENSE`` draws 4 and 5)
    stacked = stacked_normal(n_layers, cfg.dtype)
    leaves = {
        "wq": stacked(lkeys[0], (d, h, (2 if cfg.attn_out_gate is True
                                        else 1) * dh)),
        "wk": stacked(lkeys[1], (d, kv, dh)),
        "wv": stacked(lkeys[2], (d, kv, dh)),
        "wo": stacked(lkeys[3], (h, dh, d)),
    }
    if cfg.attn_out_gate == "head":
        leaves["wg"] = stacked(jax.random.fold_in(key, 14), (d, h))
    if cfg.qk_norm:
        leaves["q_norm"] = norm_start(cfg, (n_layers, dh))
        leaves["k_norm"] = norm_start(cfg, (n_layers, dh))
    return leaves


def _specs(cfg, options: Dict) -> Dict:
    by_heads = P(None, None, "tp", None)
    specs = {"wq": by_heads, "wk": by_heads, "wv": by_heads,
             "wo": P(None, "tp", None, None)}
    if cfg.attn_out_gate == "head":
        specs["wg"] = P(None, None, "tp")
    if cfg.qk_norm:
        specs["q_norm"] = specs["k_norm"] = P(None, None)
    return specs


def _attention_core(q, k, v, mesh, cfg, mask=CAUSAL):
    if (cfg.context_parallel and mesh is not None and
            mesh.shape.get("sp", 1) > 1):
        if mask != CAUSAL or k.shape[2] != q.shape[2]:
            raise ValueError("ring attention is causal and multi-head only")
        fn = jax.shard_map(
            functools.partial(ring_attention, axis_name="sp", causal=True),
            mesh=mesh,
            in_specs=(P("dp", "sp", "tp", None),) * 3,
            out_specs=P("dp", "sp", "tp", None),
            check_vma=False)
        return fn(q, k, v)
    return flash_or_ref_attention(q, k, v, mask=mask)


def _mha(h, lp: Dict, call: LayerCall):
    """The layer's normed input -> (what attention adds to the residual,
    what it counted: the mean output gate where it has one, None).  The
    run's heads are ``wq``'s, its window and table ``mha_run``'s."""
    cfg, mask = call.cfg, call.mask
    eps = cfg.norm_eps
    _, window, table = mha_run(cfg, call.run, call.options)
    if window is not None:
        if mask != CAUSAL:
            raise ValueError(f"{call.run!r} brings its own mask")
        mask = window
    q = jnp.einsum("bsd,dhk->bshk", h, lp["wq"])
    k = jnp.einsum("bsd,dhk->bshk", h, lp["wk"])
    # The names here and below are cut points a rematerialised layer may
    # keep (``models/remat.py``): q, k and v as they enter the kernel.
    # The projections before a norm carry none: kept, q's cost the
    # block-diffusion step 38 ms of copies between layouts to spare 16
    # (PERF.md section 6, PR 38).
    v = checkpoint_name(jnp.einsum("bsd,dhk->bshk", h, lp["wv"]), "attn_v")
    if cfg.attn_out_gate is True:
        q, gate = q[..., :cfg.head_dim], q[..., cfg.head_dim:]
    if cfg.qk_norm:
        q = _rms_norm(q, norm_weight(lp["q_norm"], cfg), eps)
        k = _rms_norm(k, norm_weight(lp["k_norm"], cfg), eps)
    if cfg.rope == "rotary":
        q = _rope(q, call.positions, table)
        k = _rope(k, call.positions, table)
    q = checkpoint_name(q, "attn_q")
    k = checkpoint_name(k, "attn_k")
    o = _attention_core(q, k, v, call.mesh, cfg, mask)
    counted = {}
    if cfg.attn_out_gate:
        # (a window run's gates are counted apart from the others')
        name = "attn_gate_mean" if window is None else "attn_window_gate_mean"
        with jax.named_scope("attn_gate"):
            if cfg.attn_out_gate == "head":
                gate = checkpoint_name(jnp.einsum(
                    "bsd,dh->bsh", h, lp["wg"],
                    preferred_element_type=jnp.float32), "attn_head_gate")
                gate = jax.nn.sigmoid(gate)[..., None]
            else:
                gate = jax.nn.sigmoid(gate.astype(jnp.float32))
            o = (o.astype(jnp.float32) * gate).astype(o.dtype)
            counted[name] = jnp.mean(gate)
    return jnp.einsum("bshk,hkd->bsd", o, lp["wo"]), counted, None


# (a run under a window says so to the device trace: the step's manifest
# tells its layers' time from the other ``mha`` runs')
MHA = LayerKind(
    "mha", _init, _specs, _mha,
    options={"heads": int, "window": int, "rope": str}, check=_check,
    run_scope=lambda options: jax.named_scope("mha_window")
    if "window" in options else contextlib.nullcontext())
