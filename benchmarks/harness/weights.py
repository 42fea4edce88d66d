"""Weights from the seed, made by the benchmark (not by the program
under test) on the device in one jitted call, in the type they are
trained in.  The program is handed these; the plain reference makes the
same ones again for itself.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def seed_key(seed: int) -> jax.Array:
    """A key from any whole number (``--seed`` can pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def dense_decoder_shapes(cfg: dict) -> dict:
    """Leaf name -> (shape, stddev or None for a norm's ones).  Layer
    leaves are stacked on a leading depth axis."""
    d, h, f = cfg["hidden_size"], cfg["num_attention_heads"], \
        cfg["intermediate_size"]
    dh = cfg.get("head_dim", d // h)
    nl, v = cfg["num_hidden_layers"], cfg["vocab_size"]
    std = cfg.get("initializer_range", 0.02)
    return {
        "embed": ((v, d), std),
        "layers": {
            "ln1": ((nl, d), None), "ln2": ((nl, d), None),
            "wq": ((nl, d, h, dh), std), "wk": ((nl, d, h, dh), std),
            "wv": ((nl, d, h, dh), std), "wo": ((nl, h, dh, d), std),
            "w1": ((nl, d, f), std), "w3": ((nl, d, f), std),
            "w2": ((nl, f, d), std),
        },
        "ln_f": ((d,), None),
        "lm_head": ((d, v), std),
    }


def _is_leaf(x):
    return isinstance(x, tuple)


@functools.lru_cache(maxsize=None)
def _maker(cfg_items: tuple, dtype):
    shapes = dense_decoder_shapes(dict(cfg_items))
    leaves, treedef = jax.tree.flatten(shapes, is_leaf=_is_leaf)

    def make(key):
        keys = jax.random.split(key, len(leaves))
        out = [jnp.ones(shape, jnp.float32) if std is None else
               (std * jax.random.normal(k, shape, jnp.float32)).astype(dtype)
               for k, (shape, std) in zip(keys, leaves)]
        return jax.tree.unflatten(treedef, out)

    return jax.jit(make)


_SIZE_KEYS = ("hidden_size", "num_attention_heads", "intermediate_size",
              "head_dim", "num_hidden_layers", "vocab_size",
              "initializer_range")


def make_dense_decoder(seed: int, cfg: dict, dtype=jnp.bfloat16) -> dict:
    """Norm weights are float32 ones; every matrix is N(0, std) rounded
    to ``dtype``.  One jitted call, on the device."""
    items = tuple((k, cfg[k]) for k in _SIZE_KEYS if k in cfg)
    return _maker(items, jnp.dtype(dtype))(seed_key(seed))
