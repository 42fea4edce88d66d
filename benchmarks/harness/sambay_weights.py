"""Weights from the seed for a slice of a decoder-hybrid-decoder (Mamba,
differential attention under a window or over everything before, Gated
Memory Units and differential cross attention over one layer's keys and
values), made by the benchmark on the device in one jitted call, as
``weights.make_dense_decoder`` makes the dense tree's.  The program is
handed these; the plain reference makes the same ones again for itself.

``layer_plan`` is the one place where the benchmark reads which layer is
which off the configuration file: the driver builds the program's layer
pattern from it, the reference its layers, the costs their counts.

The tree follows the layer pattern: ``layers`` is a tuple of one stack a
layer (runs of count 1), each leaf ``[1, ...]``; the head is the
embedding, so there is no ``lm_head``.
"""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp

from benchmarks.harness.weights import seed_key

#: What a leaf starts as where it is no N(0, std) matrix.
ONES, ZEROS, LOG_RANGE, DT_BIAS, DT_UNIFORM, LAMBDA = (
    "ones", "zeros", "log_range", "dt_bias", "dt_uniform", "lambda")


def layer_plan(cfg: dict) -> list:
    """[{index, kind, window, writes, reads}] for the configuration's
    ``layer_indices``.  By published index l of ``published.
    num_hidden_layers`` layers, half of them the self-decoder: even l is
    a state-space layer -- Mamba up to the half-way layer, which hands
    its scan output on, a Gated Memory Unit after it -- and odd l
    differential attention: under the window before the half, causal and
    full (and handing on its keys and values) in the layer after the
    half-way one, cross attention over those after that."""
    half = cfg["published"]["num_hidden_layers"] // 2
    plan = []
    for index in cfg["layer_indices"]:
        entry = dict(index=index, window=None, writes=None, reads=None)
        if index % cfg["mb_per_layer"] == 0:
            entry["kind"] = "mamba" if index <= half else "gmu"
            if index == half:
                entry["writes"] = "memory"
            if index > half:
                entry["reads"] = "memory"
        else:
            entry["kind"] = "diff"
            if index < half:
                entry["window"] = cfg["sliding_window"]
            elif index == half + 1:
                entry["writes"] = "kv"
            else:
                entry["reads"] = "kv"
        plan.append(entry)
    return plan


def _norms(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    return {"ln1": ((1, d), ONES), "ln1_b": ((1, d), ZEROS),
            "ln2": ((1, d), ONES), "ln2_b": ((1, d), ZEROS)}


def _swiglu(cfg: dict) -> dict:
    d, f, std = (cfg["hidden_size"], cfg["intermediate_size"],
                 cfg["initializer_range"])
    return {"w1": ((1, d, f), std), "w3": ((1, d, f), std),
            "w2": ((1, f, d), std)}


def _mixer(cfg: dict, entry: dict) -> dict:
    d, std = cfg["hidden_size"], cfg["initializer_range"]
    e, n, taps = (cfg["mamba_d_inner"], cfg["mamba_d_state"],
                  cfg["mamba_d_conv"])
    r = cfg["mamba_dt_rank"]
    if entry["kind"] == "mamba":
        return {"mamba": {
            "w_in": ((1, d, 2 * e), std), "conv": ((1, e, taps), std),
            "conv_b": ((1, e), ZEROS), "w_x": ((1, e, r + 2 * n), std),
            "w_dt": ((1, r, e), DT_UNIFORM), "dt_b": ((1, e), DT_BIAS),
            "A_log": ((1, e, n), LOG_RANGE), "D": ((1, e), ONES),
            "w_out": ((1, e, d), std)}}
    if entry["kind"] == "gmu":
        return {"gmu": {"w_in": ((1, d, e), std), "w_out": ((1, e, d), std)}}
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh = d // h
    out = {"wq": ((1, d, h, dh), std), "bq": ((1, h, dh), ZEROS),
           "wo": ((1, h // 2, 2 * dh, d), std), "bo": ((1, d), ZEROS),
           "lambda_q1": ((1, dh), LAMBDA), "lambda_k1": ((1, dh), LAMBDA),
           "lambda_q2": ((1, dh), LAMBDA), "lambda_k2": ((1, dh), LAMBDA),
           "subln": ((1, 2 * dh), ONES)}
    if entry["reads"] is None:
        out.update({"wk": ((1, d, kv, dh), std), "bk": ((1, kv, dh), ZEROS),
                    "wv": ((1, d, kv, dh), std), "bv": ((1, kv, dh), ZEROS)})
    return {"diff": out}


def sambay_shapes(cfg: dict) -> dict:
    """Leaf name -> (shape, N(0, std)'s std or what else it starts as)."""
    d, v, std = cfg["hidden_size"], cfg["vocab_size"], \
        cfg["initializer_range"]
    return {
        "embed": ((v, d), std),
        "layers": tuple({**_norms(cfg), **_mixer(cfg, entry), **_swiglu(cfg)}
                        for entry in layer_plan(cfg)),
        "ln_f": ((d,), ONES), "ln_f_b": ((d,), ZEROS),
    }


def _is_leaf(x):
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple) \
        and all(isinstance(n, int) for n in x[0])


def _draw(key, shape, how, dtype, cfg):
    f32 = jnp.float32
    if how == ONES:
        return jnp.ones(shape, f32)
    if how == ZEROS:
        return jnp.zeros(shape, f32)
    if how == LOG_RANGE:
        return jnp.broadcast_to(
            jnp.log(jnp.arange(1, shape[-1] + 1, dtype=f32)), shape)
    if how == DT_BIAS:
        step = jnp.exp(jax.random.uniform(
            key, shape, f32, math.log(cfg["mamba_dt_min"]),
            math.log(cfg["mamba_dt_max"])))
        return step + jnp.log(-jnp.expm1(-step))
    if how == DT_UNIFORM:
        bound = shape[-2] ** -0.5
        return jax.random.uniform(key, shape, f32, -bound,
                                  bound).astype(dtype)
    if how == LAMBDA:
        return cfg["lambda_std"] * jax.random.normal(key, shape, f32)
    return (how * jax.random.normal(key, shape, f32)).astype(dtype)


_SIZE_KEYS = ("hidden_size", "num_attention_heads", "num_key_value_heads",
              "intermediate_size", "mamba_d_inner", "mamba_d_state",
              "mamba_d_conv", "mamba_dt_rank", "mamba_dt_min", "mamba_dt_max",
              "lambda_std", "sliding_window", "mb_per_layer", "vocab_size",
              "initializer_range", "layer_indices", "published")


@functools.lru_cache(maxsize=None)
def _maker(sizes: str, dtype):
    cfg = json.loads(sizes)
    leaves, treedef = jax.tree.flatten(sambay_shapes(cfg), is_leaf=_is_leaf)

    def make(key):
        keys = jax.random.split(key, len(leaves))
        return jax.tree.unflatten(treedef, [
            _draw(k, shape, how, dtype, cfg)
            for k, (shape, how) in zip(keys, leaves)])

    return jax.jit(make)


def make_sambay(seed: int, cfg: dict, dtype=jnp.bfloat16) -> dict:
    """Every matrix and the convolution's taps N(0, std) rounded to
    ``dtype``, ``w_dt`` U(+-R^-1/2) likewise; float32: LayerNorm weights
    1 and biases 0, every projection bias 0, ``A_log = log(1 .. N)`` a
    channel, ``D`` 1, ``dt_b`` the inverse softplus of ``exp(U(log
    dt_min, log dt_max))``, the four lambda vectors N(0, lambda_std),
    ``subln`` 1.  One jitted call, on the device."""
    sizes = json.dumps({k: cfg[k] for k in _SIZE_KEYS}, sort_keys=True)
    return _maker(sizes, jnp.dtype(dtype))(seed_key(seed))
