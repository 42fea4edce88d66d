"""Weights from the seed for the latent-attention sparse-expert decoder
with a multi-token-prediction module (one expert-parallel rank's share),
made by the benchmark on the device in one jitted call, as
``weights.make_dense_decoder`` makes the dense tree's.  The program is
handed these; the plain reference makes the same ones again for itself.

The tree follows the layer pattern: ``layers`` is a tuple of stacks, the
``first_k_dense_replace`` dense-FFN layers and then the expert layers,
and ``mtp`` holds the module (its one layer a stack of one).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmarks.harness.weights import seed_key


def _attention_shapes(cfg: dict, nl: int) -> dict:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    rq, rkv = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    std = cfg["initializer_range"]
    return {"wq_a": ((nl, d, rq), std), "q_norm": ((nl, rq), None),
            "wq_b": ((nl, rq, h, dn + dr), std),
            "wkv_a": ((nl, d, rkv + dr), std), "kv_norm": ((nl, rkv), None),
            "wkv_b": ((nl, rkv, h, dn + dv), std),
            "wo": ((nl, h, dv, d), std)}


def _stack_shapes(cfg: dict, nl: int, experts: bool) -> dict:
    d, std = cfg["hidden_size"], cfg["initializer_range"]
    stack = {"ln1": ((nl, d), None), "ln2": ((nl, d), None),
             "mla": _attention_shapes(cfg, nl)}
    if not experts:
        f = cfg["intermediate_size"]
        stack.update({"w1": ((nl, d, f), std), "w3": ((nl, d, f), std),
                      "w2": ((nl, f, d), std)})
        return stack
    f, e, held = (cfg["moe_intermediate_size"], cfg["n_routed_experts"],
                  cfg["n_routed_experts_held"])
    fs = f * cfg["n_shared_experts"]
    stack["moe"] = {"wr": ((nl, d, e), std),
                    "w1": ((nl, held, d, f), std),
                    "w3": ((nl, held, d, f), std),
                    "w2": ((nl, held, f, d), std),
                    "ws1": ((nl, d, fs), std), "ws3": ((nl, d, fs), std),
                    "ws2": ((nl, fs, d), std)}
    return stack


def latent_moe_shapes(cfg: dict) -> dict:
    """Leaf name -> (shape, stddev or None for a norm's ones).  Layer
    leaves are stacked on a leading depth axis, a stack a kind."""
    d, v, std = cfg["hidden_size"], cfg["vocab_size"], \
        cfg["initializer_range"]
    dense = cfg["first_k_dense_replace"]
    return {
        "embed": ((v, d), std),
        "layers": (_stack_shapes(cfg, dense, False),
                   _stack_shapes(cfg, cfg["num_hidden_layers"] - dense,
                                 True)),
        "ln_f": ((d,), None),
        "lm_head": ((d, v), std),
        "mtp": {"hnorm": ((d,), None), "enorm": ((d,), None),
                "w_eh": ((2 * d, d), std),
                "layers": _stack_shapes(cfg, 1, True),
                "ln_f": ((d,), None)},
    }


def _is_leaf(x):
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple)


@functools.lru_cache(maxsize=None)
def _maker(cfg_items: tuple, dtype):
    leaves, treedef = jax.tree.flatten(latent_moe_shapes(dict(cfg_items)),
                                       is_leaf=_is_leaf)

    def make(key):
        keys = jax.random.split(key, len(leaves))
        out = [jnp.ones(shape, jnp.float32) if std is None else
               (std * jax.random.normal(k, shape, jnp.float32)).astype(dtype)
               for k, (shape, std) in zip(keys, leaves)]
        return jax.tree.unflatten(treedef, out)

    return jax.jit(make)


_SIZE_KEYS = ("hidden_size", "num_attention_heads", "q_lora_rank",
              "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
              "v_head_dim", "intermediate_size", "moe_intermediate_size",
              "n_routed_experts", "n_routed_experts_held",
              "n_shared_experts", "first_k_dense_replace",
              "num_hidden_layers", "vocab_size", "initializer_range")


def make_latent_moe(seed: int, cfg: dict, dtype=jnp.bfloat16) -> dict:
    """Norm weights are float32 ones; every matrix is N(0, std) rounded
    to ``dtype``.  One jitted call, on the device."""
    items = tuple((k, cfg[k]) for k in _SIZE_KEYS)
    return _maker(items, jnp.dtype(dtype))(seed_key(seed))
