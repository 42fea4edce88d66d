"""Weights from the seed for the sparse-expert decoder's tree (one
expert-parallel rank's share), made by the benchmark on the device in
one jitted call, as ``weights.make_dense_decoder`` makes the dense
tree's.  The program is handed these; the plain reference makes the
same ones again for itself.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmarks.harness.weights import seed_key


def sparse_decoder_shapes(cfg: dict) -> dict:
    """Leaf name -> (shape, stddev or None for a norm's ones).  Layer
    leaves are stacked on a leading depth axis; the expert leaves hold
    ``num_experts_held`` experts, the router all ``num_experts``."""
    d, h, kv, dh = (cfg["hidden_size"], cfg["num_attention_heads"],
                    cfg["num_key_value_heads"], cfg["head_dim"])
    f, e, held = (cfg["moe_intermediate_size"], cfg["num_experts"],
                  cfg["num_experts_held"])
    nl, v = cfg["num_hidden_layers"], cfg["vocab_size"]
    std = cfg["initializer_range"]
    return {
        "embed": ((v, d), std),
        "layers": {
            "ln1": ((nl, d), None), "ln2": ((nl, d), None),
            "q_norm": ((nl, dh), None), "k_norm": ((nl, dh), None),
            "wq": ((nl, d, h, dh), std), "wk": ((nl, d, kv, dh), std),
            "wv": ((nl, d, kv, dh), std), "wo": ((nl, h, dh, d), std),
            "moe": {"wr": ((nl, d, e), std),
                    "w1": ((nl, held, d, f), std),
                    "w3": ((nl, held, d, f), std),
                    "w2": ((nl, held, f, d), std)},
        },
        "ln_f": ((d,), None),
        "lm_head": ((d, v), std),
    }


@functools.lru_cache(maxsize=None)
def _maker(cfg_items: tuple, dtype):
    shapes = sparse_decoder_shapes(dict(cfg_items))
    leaves, treedef = jax.tree.flatten(
        shapes, is_leaf=lambda x: isinstance(x, tuple))

    def make(key):
        keys = jax.random.split(key, len(leaves))
        out = [jnp.ones(shape, jnp.float32) if std is None else
               (std * jax.random.normal(k, shape, jnp.float32)).astype(dtype)
               for k, (shape, std) in zip(keys, leaves)]
        return jax.tree.unflatten(treedef, out)

    return jax.jit(make)


_SIZE_KEYS = ("hidden_size", "num_attention_heads", "num_key_value_heads",
              "head_dim", "moe_intermediate_size", "num_experts",
              "num_experts_held", "num_hidden_layers", "vocab_size",
              "initializer_range")


def make_sparse_decoder(seed: int, cfg: dict, dtype=jnp.bfloat16) -> dict:
    """Norm weights are float32 ones; every matrix is N(0, std) rounded
    to ``dtype``.  One jitted call, on the device."""
    items = tuple((k, cfg[k]) for k in _SIZE_KEYS)
    return _maker(items, jnp.dtype(dtype))(seed_key(seed))
