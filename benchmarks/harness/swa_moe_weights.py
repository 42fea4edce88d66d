"""Weights from the seed for a decoder whose attention layers are of two
kinds -- a window and everything before, each with its own head count --
with a head-wise output gate, a leading dense layer and sparse expert
layers beside a shared expert (one expert-parallel rank's share), made
by the benchmark on the device in one jitted call, as
``weights.make_dense_decoder`` makes the dense tree's.  The program is
handed these; the plain reference makes the same ones again for itself.

The tree follows the layer pattern (``pattern_of``): ``layers`` is a
tuple of entries, a run's stack ``[layers of the run, ...]`` or, for a
period, a tuple of its runs' stacks, each ``[periods, layers of the
run, ...]``.
"""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp

from benchmarks.harness.weights import seed_key

ONES = "ones"


def layer_plan(cfg: dict) -> list:
    """The first ``num_hidden_layers`` layers as the configuration's
    per-layer lists have them: ``{"index", "type", "heads", "window",
    "ffn"}`` a layer (``window`` None on a ``full_attention`` layer)."""
    out = []
    for l in range(cfg["num_hidden_layers"]):
        kind = cfg["layer_types"][l]
        if kind not in ("full_attention", "sliding_attention"):
            raise ValueError(f"layer {l}: {kind!r}")
        out.append({
            "index": l, "type": kind,
            "heads": cfg["num_attention_heads_per_layer"][l],
            "window": cfg["sliding_window"]
            if kind == "sliding_attention" else None,
            "ffn": cfg["mlp_layer_types"][l]})
    return out


def pattern_of(cfg: dict) -> list:
    """The plan as runs of alike neighbours, ``[(layer, count), ...]``
    or ``([(layer, count), ...], repeats)`` an entry: the leading dense
    layers as runs, then the expert layers as the shortest period that
    repeats to make them."""
    runs = []
    for entry in layer_plan(cfg):
        like = {k: entry[k] for k in ("type", "heads", "window", "ffn")}
        if runs and runs[-1][0] == like:
            runs[-1][1] += 1
        else:
            runs.append([like, 1])
    runs = [(like, count) for like, count in runs]
    lead = 0
    while lead < len(runs) and runs[lead][0]["ffn"] == "dense":
        lead += 1
    tail = runs[lead:]
    if not tail:
        return runs
    period = next(n for n in range(1, len(tail) + 1)
                  if len(tail) % n == 0
                  and tail == tail[:n] * (len(tail) // n))
    return runs[:lead] + [(tail[:period], len(tail) // period)]


def _stack_shapes(cfg: dict, like: dict, lead: tuple) -> dict:
    d, std = cfg["hidden_size"], cfg["initializer_range"]
    h, kv, dh = like["heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    out = {"ln1": (lead + (d,), ONES), "ln2": (lead + (d,), ONES),
           "wq": (lead + (d, h, dh), std), "wk": (lead + (d, kv, dh), std),
           "wv": (lead + (d, kv, dh), std), "wo": (lead + (h, dh, d), std),
           "wg": (lead + (d, h), std)}
    if like["ffn"] == "dense":
        f = cfg["intermediate_size"]
        out.update({"w1": (lead + (d, f), std), "w3": (lead + (d, f), std),
                    "w2": (lead + (f, d), std)})
        return out
    f, e, held = (cfg["moe_intermediate_size"], cfg["num_experts"],
                  cfg["num_experts_held"])
    fs = cfg["shared_expert_intermediate_size"]
    out["moe"] = {"wr": (lead + (d, e), std),
                  "w1": (lead + (held, d, f), std),
                  "w3": (lead + (held, d, f), std),
                  "w2": (lead + (held, f, d), std),
                  "ws1": (lead + (d, fs), std), "ws3": (lead + (d, fs), std),
                  "ws2": (lead + (fs, d), std)}
    return out


def decoder_shapes(cfg: dict) -> dict:
    """Leaf name -> (shape, N(0, std)'s std or ``ONES``)."""
    d, v, std = cfg["hidden_size"], cfg["vocab_size"], \
        cfg["initializer_range"]
    layers = []
    for entry in pattern_of(cfg):
        if isinstance(entry[0], dict):
            layers.append(_stack_shapes(cfg, entry[0], (entry[1],)))
        else:
            runs, repeats = entry
            layers.append(tuple(_stack_shapes(cfg, like, (repeats, count))
                                for like, count in runs))
    return {"embed": ((v, d), std), "layers": tuple(layers),
            "ln_f": ((d,), ONES), "lm_head": ((d, v), std)}


def _is_leaf(x):
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple) \
        and all(isinstance(n, int) for n in x[0])


def parameter_count(cfg: dict) -> int:
    """Parameters of the tree as built."""
    return sum(math.prod(shape) for shape, _ in jax.tree.leaves(
        decoder_shapes(cfg), is_leaf=_is_leaf))


@functools.lru_cache(maxsize=None)
def _maker(cfg_json: str, dtype):
    leaves, treedef = jax.tree.flatten(decoder_shapes(json.loads(cfg_json)),
                                       is_leaf=_is_leaf)

    def make(key):
        keys = jax.random.split(key, len(leaves))
        return jax.tree.unflatten(treedef, [
            jnp.ones(shape, jnp.float32) if how == ONES else
            (how * jax.random.normal(k, shape, jnp.float32)).astype(dtype)
            for k, (shape, how) in zip(keys, leaves)])

    return jax.jit(make)


_SIZE_KEYS = ("hidden_size", "num_key_value_heads", "head_dim",
              "intermediate_size", "moe_intermediate_size",
              "shared_expert_intermediate_size", "num_experts",
              "num_experts_held", "num_hidden_layers", "vocab_size",
              "initializer_range", "layer_types", "mlp_layer_types",
              "num_attention_heads_per_layer", "sliding_window")


def make_decoder(seed: int, cfg: dict, dtype=jnp.bfloat16) -> dict:
    """Every matrix N(0, std) rounded to ``dtype``, the norms' weights
    float32 ones.  One jitted call, on the device."""
    sizes = json.dumps({k: cfg[k] for k in _SIZE_KEYS}, sort_keys=True)
    return _maker(sizes, jnp.dtype(dtype))(seed_key(seed))
