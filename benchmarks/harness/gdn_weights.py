"""Weights from the seed for the hybrid delta-rule / gated-attention
sparse-expert decoder (one expert-parallel rank's share), made by the
benchmark on the device in one jitted call, as
``weights.make_dense_decoder`` makes the dense tree's.  The program is
handed these; the plain reference makes the same ones again for itself.

The tree follows the layer pattern: ``layers`` is a tuple of one entry,
the period, which is a tuple of its two runs' stacks -- the delta layers
and the gated-attention layer -- each ``[periods, layers of the run,
...]``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmarks.harness.weights import seed_key

#: What a leaf starts as where it is no N(0, std) matrix.
ONES, ZEROS, LOG_UNIFORM_16 = "ones", "zeros", "log_uniform_16"


def period_of(cfg: dict):
    """(periods, delta layers a period): ``full_attention_interval - 1``
    delta layers and then one attention layer, repeated."""
    interval = cfg["full_attention_interval"]
    if cfg["num_hidden_layers"] % interval:
        raise ValueError("the depth is whole periods")
    return cfg["num_hidden_layers"] // interval, interval - 1


def _expert_shapes(cfg: dict, lead: tuple) -> dict:
    d, std = cfg["hidden_size"], cfg["initializer_range"]
    f, e, held = (cfg["moe_intermediate_size"], cfg["num_experts"],
                  cfg["num_experts_held"])
    fs = cfg["shared_expert_intermediate_size"]
    return {"wr": (lead + (d, e), std),
            "w1": (lead + (held, d, f), std),
            "w3": (lead + (held, d, f), std),
            "w2": (lead + (held, f, d), std),
            "ws1": (lead + (d, fs), std), "ws3": (lead + (d, fs), std),
            "ws2": (lead + (fs, d), std), "wsg": (lead + (d, 1), std)}


def _delta_shapes(cfg: dict, lead: tuple) -> dict:
    d, std = cfg["hidden_size"], cfg["initializer_range"]
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    r = hv // hk
    return {"ln1": (lead + (d,), ZEROS), "ln2": (lead + (d,), ZEROS),
            "gdn": {
                "w_qkvz": (lead + (d, hk, 2 * dk + 2 * r * dv), std),
                "w_ba": (lead + (d, hk, 2 * r), std),
                "conv": (lead + (hk, 2 * dk + r * dv,
                                 cfg["linear_conv_kernel_dim"]), std),
                "A_log": (lead + (hk, r), LOG_UNIFORM_16),
                "dt_bias": (lead + (hk, r), ONES),
                "norm": (lead + (dv,), ONES),
                "wo": (lead + (hk, r * dv, d), std)},
            "moe": _expert_shapes(cfg, lead)}


def _attention_shapes(cfg: dict, lead: tuple) -> dict:
    d, std = cfg["hidden_size"], cfg["initializer_range"]
    h, kv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    return {"ln1": (lead + (d,), ZEROS), "ln2": (lead + (d,), ZEROS),
            "q_norm": (lead + (dh,), ZEROS), "k_norm": (lead + (dh,), ZEROS),
            "wq": (lead + (d, h, 2 * dh), std),
            "wk": (lead + (d, kv, dh), std), "wv": (lead + (d, kv, dh), std),
            "wo": (lead + (h, dh, d), std),
            "moe": _expert_shapes(cfg, lead)}


def hybrid_shapes(cfg: dict) -> dict:
    """Leaf name -> (shape, N(0, std)'s std or what else it starts as)."""
    d, v, std = cfg["hidden_size"], cfg["vocab_size"], \
        cfg["initializer_range"]
    periods, delta = period_of(cfg)
    return {
        "embed": ((v, d), std),
        "layers": ((_delta_shapes(cfg, (periods, delta)),
                    _attention_shapes(cfg, (periods, 1))),),
        "ln_f": ((d,), ZEROS),
        "lm_head": ((d, v), std),
    }


def _is_leaf(x):
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple) \
        and all(isinstance(n, int) for n in x[0])


def _draw(key, shape, how, dtype):
    if how == ONES:
        return jnp.ones(shape, jnp.float32)
    if how == ZEROS:
        return jnp.zeros(shape, jnp.float32)
    if how == LOG_UNIFORM_16:
        return jnp.log(jax.random.uniform(key, shape, jnp.float32,
                                          1e-6, 16.0))
    return (how * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


@functools.lru_cache(maxsize=None)
def _maker(cfg_items: tuple, dtype):
    leaves, treedef = jax.tree.flatten(hybrid_shapes(dict(cfg_items)),
                                       is_leaf=_is_leaf)

    def make(key):
        keys = jax.random.split(key, len(leaves))
        return jax.tree.unflatten(treedef, [
            _draw(k, shape, how, dtype)
            for k, (shape, how) in zip(keys, leaves)])

    return jax.jit(make)


_SIZE_KEYS = ("hidden_size", "num_attention_heads", "num_key_value_heads",
              "head_dim", "linear_num_key_heads", "linear_num_value_heads",
              "linear_key_head_dim", "linear_value_head_dim",
              "linear_conv_kernel_dim", "moe_intermediate_size",
              "shared_expert_intermediate_size", "num_experts",
              "num_experts_held", "num_hidden_layers",
              "full_attention_interval", "vocab_size", "initializer_range")


def make_hybrid(seed: int, cfg: dict, dtype=jnp.bfloat16) -> dict:
    """Every matrix and the convolution's taps N(0, std) rounded to
    ``dtype``; the ``(1 + w)`` norms' weights float32 noughts, the delta
    layers' output norm and ``dt_bias`` float32 ones, ``A_log = log U(0,
    16)`` float32.  One jitted call, on the device."""
    items = tuple((k, cfg[k]) for k in _SIZE_KEYS)
    return _maker(items, jnp.dtype(dtype))(seed_key(seed))
