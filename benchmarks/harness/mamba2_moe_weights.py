"""Weights from the seed for a decoder whose layers are each ONE
sublayer -- a Mamba-2 mixer, an expert layer whose experts work in a
latent, or grouped-query attention without positional encoding -- as
Nemotron-H's ``hybrid_override_pattern`` lays them out (one
expert-parallel rank's share), made by the benchmark on the device in
one jitted call, as ``swa_moe_weights.make_decoder`` makes its tree's.
The program is handed these; the plain reference makes the same ones
again for itself.

The program's layer has a mixer and an FFN (``models/transformer.py``),
so the published layers pair up (``layer_plan``): a mixer takes the
expert layer after it, and a mixer followed by another mixer is a layer
alone (FFN kind ``none``: no second norm).  ``MEMEMEM*EME`` is the
program's six layers ``M+E, M+E, M+E, M, *+E, M+E``.  The tree follows
the runs of alike neighbours (``pattern_of``): ``layers`` is a tuple of
stacks ``[layers of the run, ...]``.
"""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp

from benchmarks.harness.weights import seed_key

ONES = ("ones",)
#: The mixers' mark in ``hybrid_override_pattern``, by the program's kind.
MIXERS = {"M": "mamba2", "*": "mha"}


def layer_plan(cfg: dict) -> list:
    """The first ``num_hidden_layers`` published layers paired as the
    program's: ``{"index", "mixer", "ffn"}`` a layer (``index``: the
    published index of its mixer; ``ffn`` ``moe`` or ``none``)."""
    marks = cfg["hybrid_override_pattern"][:cfg["num_hidden_layers"]]
    out, i = [], 0
    while i < len(marks):
        if marks[i] not in MIXERS:
            raise ValueError(f"layer {i}: {marks[i]!r} follows no mixer")
        paired = i + 1 < len(marks) and marks[i + 1] == "E"
        out.append({"index": i, "mixer": MIXERS[marks[i]],
                    "ffn": "moe" if paired else "none"})
        i += 2 if paired else 1
    return out


def pattern_of(cfg: dict) -> list:
    """The plan as runs of alike neighbours: ``[(mixer, ffn, count),
    ...]``, the program's ``layer_pattern``."""
    runs = []
    for entry in layer_plan(cfg):
        if runs and runs[-1][:2] == [entry["mixer"], entry["ffn"]]:
            runs[-1][2] += 1
        else:
            runs.append([entry["mixer"], entry["ffn"], 1])
    return [tuple(run) for run in runs]


def _normal(std):
    return ("normal", std)


def _stack_shapes(cfg: dict, mixer: str, ffn: str, lead: tuple) -> dict:
    """Leaf -> (shape, how it is drawn): ``("normal", std)``, ``ONES``,
    ``("uniform", low, high)``, ``("a_log",)`` (log U(1, 16)) or
    ``("dt_bias",)`` (the inverse softplus of a step size drawn
    log-uniformly in the configuration's range, floored)."""
    d, std = cfg["hidden_size"], _normal(cfg["initializer_range"])
    out = {"ln1": (lead + (d,), ONES)}
    if mixer == "mamba2":
        h, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
        gn = cfg["n_groups"] * cfg["ssm_state_size"]
        conv, taps = h * p + 2 * gn, cfg["conv_kernel"]
        bound = taps ** -0.5
        out["mamba2"] = {
            "w_z": (lead + (d, h * p), std),
            "w_xbc": (lead + (d, conv), std),
            "w_dt": (lead + (d, h), std),
            "conv": (lead + (conv, taps), ("uniform", -bound, bound)),
            "conv_b": (lead + (conv,), ("uniform", -bound, bound)),
            "dt_bias": (lead + (h,), ("dt_bias",)),
            "A_log": (lead + (h,), ("a_log",)),
            "D": (lead + (h,), ONES),
            "norm": (lead + (h * p,), ONES),
            "w_out": (lead + (h * p, d), std)}
    else:
        hq, kv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                      cfg["head_dim"])
        out.update({"wq": (lead + (d, hq, dh), std),
                    "wk": (lead + (d, kv, dh), std),
                    "wv": (lead + (d, kv, dh), std),
                    "wo": (lead + (hq, dh, d), std)})
    if ffn == "moe":
        e, held = cfg["n_routed_experts"], cfg["n_routed_experts_held"]
        f, lat = cfg["moe_intermediate_size"], cfg["moe_latent_size"]
        fs = cfg["moe_shared_expert_intermediate_size"]
        out["ln2"] = (lead + (d,), ONES)
        out["moe"] = {"wr": (lead + (d, e), std),
                      "w1": (lead + (held, lat, f), std),
                      "w2": (lead + (held, f, lat), std),
                      "w_down": (lead + (d, lat), std),
                      "w_up": (lead + (lat, d), std),
                      "ws1": (lead + (d, fs), std),
                      "ws2": (lead + (fs, d), std)}
    return out


def decoder_shapes(cfg: dict) -> dict:
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    std = _normal(cfg["initializer_range"])
    return {"embed": ((v, d), std),
            "layers": tuple(_stack_shapes(cfg, mixer, ffn, (count,))
                            for mixer, ffn, count in pattern_of(cfg)),
            "ln_f": ((d,), ONES), "lm_head": ((d, v), std)}


def _is_leaf(x):
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple) \
        and all(isinstance(n, int) for n in x[0])


def parameter_count(cfg: dict) -> int:
    """Parameters of the tree as built (the routers' correction bias is
    state beside it: no parameter)."""
    return sum(math.prod(shape) for shape, _ in jax.tree.leaves(
        decoder_shapes(cfg), is_leaf=_is_leaf))


def _draw(key, shape, how, dtype, cfg):
    f32 = jnp.float32
    if how == ONES:
        return jnp.ones(shape, f32)
    if how[0] == "normal":
        return (how[1] * jax.random.normal(key, shape, f32)).astype(dtype)
    if how[0] == "uniform":
        taps = jax.random.uniform(key, shape, f32, how[1], how[2])
        return taps.astype(dtype) if len(shape) > 2 else taps
    if how[0] == "a_log":
        return jnp.log(jax.random.uniform(key, shape, f32, 1.0, 16.0))
    step = jnp.maximum(jnp.exp(jax.random.uniform(
        key, shape, f32, math.log(cfg["time_step_min"]),
        math.log(cfg["time_step_max"]))), cfg["time_step_floor"])
    return step + jnp.log(-jnp.expm1(-step))


@functools.lru_cache(maxsize=None)
def _maker(cfg_json: str, dtype):
    cfg = json.loads(cfg_json)
    leaves, treedef = jax.tree.flatten(decoder_shapes(cfg), is_leaf=_is_leaf)

    def make(key):
        keys = jax.random.split(key, len(leaves))
        return jax.tree.unflatten(treedef, [
            _draw(k, shape, how, dtype, cfg)
            for k, (shape, how) in zip(keys, leaves)])

    return jax.jit(make)


_SIZE_KEYS = ("hidden_size", "num_attention_heads", "num_key_value_heads",
              "head_dim", "mamba_num_heads", "mamba_head_dim", "n_groups",
              "ssm_state_size", "conv_kernel", "n_routed_experts",
              "n_routed_experts_held", "moe_intermediate_size",
              "moe_latent_size", "moe_shared_expert_intermediate_size",
              "num_hidden_layers", "hybrid_override_pattern", "vocab_size",
              "initializer_range", "time_step_min", "time_step_max",
              "time_step_floor")


def make_decoder(seed: int, cfg: dict, dtype=jnp.bfloat16) -> dict:
    """Every matrix N(0, std) rounded to ``dtype``; the convolution's
    taps U(+-K^-1/2) rounded to it, their bias float32; ``A_log``,
    ``dt_bias``, ``D`` and the norms' weights float32.  One jitted call,
    on the device."""
    sizes = json.dumps({k: cfg[k] for k in _SIZE_KEYS}, sort_keys=True)
    return _maker(sizes, jnp.dtype(dtype))(seed_key(seed))
