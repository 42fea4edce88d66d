"""Differential attention (arXiv:2410.05258, as the Phi-4-mini-flash
modelling code has it): two softmax maps a pair of heads, the second
subtracted from the first with a learned weight, a norm over the pair's
output.

A layer-pattern kind (``"diff"``) with its parameters under
``lp["diff"]``.  For ``u [B, S, d]`` (the layer's normed input), ``H``
query heads and ``K`` K/V heads of ``Dh`` (both even), with biases:

    q | k | v   = u wq + bq | u wk + bk | u wv + bv
    heads pair off: q1_i, q2_i = query heads 2i, 2i + 1 (i < H / 2);
    k1_j, k2_j = K heads 2j, 2j + 1 (j < K / 2); V_j = [v_2j | v_2j+1],
    2 Dh wide; pair i reads pair j = i // (H / K)
    a^s_i       = softmax_mask(q^s_i k^s_j^T Dh^-1/2) V_j      s = 1, 2
    lambda      = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init
    lambda_init = 0.8 - 0.6 exp(-0.3 l)       l: the layer's index
    o_i         = (1 - lambda_init) rmsnorm(a^1_i - lambda a^2_i; subln)
    out         = [o_0 ... o_{H/2-1}] wo + bo

No positional encoding of any kind, and no ``tp`` or ``sp`` layout yet
(the kind refuses such a mesh).  The two maps are two calls of the flash
kernel pair (``ops/flash_attention.py``: ``H / 2`` query heads of
``Dh`` over ``K / 2`` key heads of ``Dh`` and value heads of ``2 Dh``,
the widths the latent-attention path already gives the kernels); one
call over all ``H`` heads would have to hold ``V`` twice in HBM.

What a run's options say (``kinds.run_options``):

* ``window=w``: the mask is ``SlidingWindow(w)``, else causal;
* ``writes=kv``: the layer also hands ``(k1, k2, V)`` to later layers;
* ``reads=kv``: cross attention -- the layer has ``wq``, ``bq``, ``wo``,
  ``bo``, the four lambda vectors and ``subln`` only, and attends over
  the keys and values it is handed, under the causal mask.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.models.common import (LayerCall, LayerKind, on_one_device,
                                   replicated, stacked_normal)
from ray_tpu.ops.attention_mask import CAUSAL, SlidingWindow
from ray_tpu.ops.flash_attention import attention as flash_or_ref_attention

_SUBLN_EPS = 1e-5


def lambda_init(index):
    """``0.8 - 0.6 exp(-0.3 l)`` of a layer's (published) index."""
    return 0.8 - 0.6 * jnp.exp(-0.3 * jnp.asarray(index, jnp.float32))


def _init(key: jax.Array, n_layers: int, cfg, options: Dict) -> Dict:
    """Matrices N(0, 0.02), biases 0, the four lambda vectors N(0, 0.1),
    ``subln`` 1.  A run that reads the keys and values projects none."""
    d, h, kv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    if h % 2 or kv % 2 or (h // 2) % (kv // 2):
        raise ValueError(f"differential attention pairs heads: {h} query "
                         f"heads over {kv} K/V heads")
    keys = jax.random.split(jax.random.fold_in(key, 13), 8)
    f32, stacked = jnp.float32, stacked_normal(n_layers, cfg.dtype)

    def lam(key):
        return 0.1 * jax.random.normal(key, (n_layers, dh), f32)

    out = {
        "wq": stacked(keys[0], (d, h, dh)),
        "bq": jnp.zeros((n_layers, h, dh), f32),
        "wo": stacked(keys[3], (h // 2, 2 * dh, d)),
        "bo": jnp.zeros((n_layers, d), f32),
        "lambda_q1": lam(keys[4]), "lambda_k1": lam(keys[5]),
        "lambda_q2": lam(keys[6]), "lambda_k2": lam(keys[7]),
        "subln": jnp.ones((n_layers, 2 * dh), f32),
    }
    if "reads" not in options:
        out.update({
            "wk": stacked(keys[1], (d, kv, dh)),
            "bk": jnp.zeros((n_layers, kv, dh), f32),
            "wv": stacked(keys[2], (d, kv, dh)),
            "bv": jnp.zeros((n_layers, kv, dh), f32),
        })
    return {"diff": out}


def _project(u, w, b):
    """``u w + b`` by head -> the heads of stream 1 and of stream 2
    (heads 2i and 2i + 1): the weight is cut, not the product."""
    def stream(s):
        y = jnp.einsum("bsd,dhk->bshk", u, w[:, s::2]) + b[s::2]
        return y.astype(u.dtype)
    return stream(0), stream(1)


def keys_and_values(u, lp: Dict):
    """-> (k1, k2 [B, S, K / 2, Dh], V [B, S, K / 2, 2 Dh])."""
    k1, k2 = _project(u, lp["wk"], lp["bk"])
    v = (jnp.einsum("bsd,dhk->bshk", u, lp["wv"]) + lp["bv"]).astype(u.dtype)
    b, s, kv, dh = v.shape
    return k1, k2, v.reshape(b, s, kv // 2, 2 * dh)


def _diff(u, lp: Dict, call: LayerCall):
    """The layer's normed input ``u [B, S, d]`` -> (what the layer adds
    to the residual, what it counted -- ``diff_lambda`` --, the keys and
    values it attended over ``(k1, k2, V)`` where its run writes them).
    ``lambda_init`` reads the layer's index; the run's ``window``:
    positions a query sees, its own among them (none: all before it); a
    run that reads attends over another layer's keys and values."""
    on_one_device(call)
    options, lp, f32 = call.options, lp["diff"], jnp.float32
    mask = SlidingWindow(options["window"]) if "window" in options else CAUSAL
    kv = call.shared["kv"] if "reads" in options else None
    q1, q2 = _project(u, lp["wq"], lp["bq"])
    q1 = checkpoint_name(q1, "diff_q")
    q2 = checkpoint_name(q2, "diff_q")
    if kv is None:
        kv = tuple(checkpoint_name(x, "diff_kv")
                   for x in keys_and_values(u, lp))
    k1, k2, v = kv
    a1 = flash_or_ref_attention(q1, k1, v, mask=mask)
    a2 = flash_or_ref_attention(q2, k2, v, mask=mask)
    with jax.named_scope("diff_attn"):
        first = lambda_init(call.index)
        lam = (jnp.exp(jnp.sum(lp["lambda_q1"] * lp["lambda_k1"]))
               - jnp.exp(jnp.sum(lp["lambda_q2"] * lp["lambda_k2"])) + first)
        o = a1.astype(f32) - lam * a2.astype(f32)
        o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                              + _SUBLN_EPS) * lp["subln"]
        o = (o * (1.0 - first)).astype(u.dtype)
    out = jnp.einsum("bshk,hkd->bsd", o, lp["wo"]) + lp["bo"]
    return (out.astype(u.dtype), {"diff_lambda": jax.lax.stop_gradient(lam)},
            kv if "writes" in options else None)


DIFF = LayerKind(
    "diff", _init, replicated(_init), _diff,
    options={"window": int, "writes": ("kv",), "reads": ("kv",)},
    single_device=True, indexed=True)

