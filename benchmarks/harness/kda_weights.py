"""Weights from the seed for a decoder of Kimi Delta Attention and latent
attention layers over dense and expert FFNs, as Ling-3.0 lays them out
(``layer_group_size``: five KDA layers, then one MLA layer, a period;
``first_k_dense_replace`` leading dense layers), one expert-parallel
rank's share, made by the benchmark on the device in one jitted call, as
``mamba2_moe_weights.make_decoder`` makes its tree's.  The program is
handed these; the plain reference makes the same ones again for itself.

The configuration keeps the published layers ``kept_layers``
(``layer_plan``); the tree follows the runs of alike neighbours
(``pattern_of``): ``layers`` is a tuple of stacks ``[layers of the run,
...]``.
"""

from __future__ import annotations

import functools
import itertools
import json
import math

import jax
import jax.numpy as jnp

from benchmarks.harness.mamba2_moe_weights import _is_leaf
from benchmarks.harness.weights import seed_key

ONES = ("ones",)


def layer_plan(cfg: dict) -> list:
    """The kept published layers as the program's: ``{"index", "mixer",
    "ffn"}`` a layer (``mixer`` ``kda`` or ``mla``, ``ffn`` ``dense`` or
    ``moe``)."""
    period, dense = cfg["layer_group_size"], cfg["first_k_dense_replace"]
    kept = cfg["kept_layers"]
    if len(kept) != cfg["num_hidden_layers"]:
        raise ValueError(f"{len(kept)} kept layers for num_hidden_layers "
                         f"{cfg['num_hidden_layers']}")
    return [{"index": i, "mixer": "mla" if (i + 1) % period == 0 else "kda",
             "ffn": "dense" if i < dense else "moe"} for i in kept]


def pattern_of(cfg: dict) -> list:
    """The plan as runs of alike neighbours: ``[(mixer, ffn, count),
    ...]``, the program's ``layer_pattern``."""
    return [(mixer, ffn, len(list(run))) for (mixer, ffn), run in
            itertools.groupby(layer_plan(cfg),
                              key=lambda e: (e["mixer"], e["ffn"]))]


def _stack_shapes(cfg: dict, mixer: str, ffn: str, lead: tuple) -> dict:
    """Leaf -> (shape, how it is drawn): ``("normal", std)``, ``ONES``,
    ``("uniform", bound)`` (taps, in the matrices' type), ``("a_log",)``
    (log U(1, 16)) or ``("dt_bias",)`` (the inverse softplus of a step
    size drawn log-uniformly between 1e-3 and 1e-1)."""
    d, std = cfg["hidden_size"], ("normal", cfg["initializer_range"])
    h = cfg["num_attention_heads"]
    out = {"ln1": (lead + (d,), ONES), "ln2": (lead + (d,), ONES)}
    if mixer == "kda":
        dk, taps = cfg["head_dim"], cfg["short_conv_kernel_size"]
        out["kda"] = {
            "w_qkv": (lead + (d, h, 3 * dk), std),
            "conv": (lead + (h, 3 * dk, taps), ("uniform", taps ** -0.5)),
            "w_alpha": (lead + (d, h, dk), std),
            "A_log": (lead + (h,), ("a_log",)),
            "dt_bias": (lead + (h, dk), ("dt_bias",)),
            "w_beta": (lead + (d, h), std),
            "w_gate": (lead + (d, h), std),
            "norm": (lead + (dk,), ONES),
            "wo": (lead + (h, dk, d), std)}
    else:
        dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
        rkv = cfg["kv_lora_rank"]
        out["mla"] = {
            "wq": (lead + (d, h, dn + dr), std),
            "q_head_norm": (lead + (dn + dr,), ONES),
            "k_head_norm": (lead + (dn + dr,), ONES),
            "wkv_a": (lead + (d, rkv + dr), std),
            "kv_norm": (lead + (rkv,), ONES),
            "wkv_b": (lead + (rkv, h, dn + dv), std),
            "wo": (lead + (h, dv, d), std)}
    if ffn == "dense":
        f = cfg["intermediate_size"]
        out.update({"w1": (lead + (d, f), std), "w3": (lead + (d, f), std),
                    "w2": (lead + (f, d), std)})
    else:
        e, held = cfg["num_experts"], cfg["num_experts_held"]
        f, fs = (cfg["moe_intermediate_size"],
                 cfg["moe_shared_expert_intermediate_size"])
        out["moe"] = {"wr": (lead + (d, e), std),
                      "w1": (lead + (held, d, f), std),
                      "w3": (lead + (held, d, f), std),
                      "w2": (lead + (held, f, d), std),
                      "ws1": (lead + (d, fs), std),
                      "ws3": (lead + (d, fs), std),
                      "ws2": (lead + (fs, d), std)}
    return out


def decoder_shapes(cfg: dict) -> dict:
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    std = ("normal", cfg["initializer_range"])
    return {"embed": ((v, d), std),
            "layers": tuple(_stack_shapes(cfg, mixer, ffn, (count,))
                            for mixer, ffn, count in pattern_of(cfg)),
            "ln_f": ((d,), ONES), "lm_head": ((d, v), std)}


def parameter_count(cfg: dict) -> int:
    """Parameters of the tree as built (the routers' correction bias is
    state beside it: no parameter)."""
    return sum(math.prod(shape) for shape, _ in jax.tree.leaves(
        decoder_shapes(cfg), is_leaf=_is_leaf))


def _draw(key, shape, how, dtype):
    f32 = jnp.float32
    if how == ONES:
        return jnp.ones(shape, f32)
    if how[0] == "normal":
        return (how[1] * jax.random.normal(key, shape, f32)).astype(dtype)
    if how[0] == "uniform":
        return jax.random.uniform(key, shape, f32, -how[1],
                                  how[1]).astype(dtype)
    if how[0] == "a_log":
        return jnp.log(jax.random.uniform(key, shape, f32, 1.0, 16.0))
    step = jnp.exp(jax.random.uniform(key, shape, f32, math.log(1e-3),
                                      math.log(1e-1)))
    return step + jnp.log(-jnp.expm1(-step))


@functools.lru_cache(maxsize=None)
def _maker(cfg_json: str, dtype):
    cfg = json.loads(cfg_json)
    leaves, treedef = jax.tree.flatten(decoder_shapes(cfg), is_leaf=_is_leaf)

    def make(key):
        keys = jax.random.split(key, len(leaves))
        return jax.tree.unflatten(treedef, [
            _draw(k, shape, how, dtype)
            for k, (shape, how) in zip(keys, leaves)])

    return jax.jit(make)


_SIZE_KEYS = ("hidden_size", "num_attention_heads", "head_dim",
              "short_conv_kernel_size", "qk_nope_head_dim",
              "qk_rope_head_dim", "v_head_dim", "kv_lora_rank",
              "intermediate_size", "num_experts", "num_experts_held",
              "moe_intermediate_size", "moe_shared_expert_intermediate_size",
              "num_hidden_layers", "kept_layers", "layer_group_size",
              "first_k_dense_replace", "vocab_size", "initializer_range")


def make_decoder(seed: int, cfg: dict, dtype=jnp.bfloat16) -> dict:
    """Every matrix N(0, std) rounded to ``dtype``, the convolution's
    taps U(+-K^-1/2) rounded to it; ``A_log``, ``dt_bias`` and the norms'
    weights float32.  One jitted call, on the device."""
    sizes = json.dumps({k: cfg[k] for k in _SIZE_KEYS}, sort_keys=True)
    return _maker(sizes, jnp.dtype(dtype))(seed_key(seed))
