"""Multi-token prediction (DeepSeek-V3's, depth 1): a module after the
stack that predicts the token after next, trained beside the next-token
loss on the same layer runs.

With ``h_i`` the stack's output at position ``i`` BEFORE the final norm
(it has seen tokens ``t_0 .. t_i``):

    h'_i  = [rmsnorm(h_i; hnorm) ; rmsnorm(embed[t_{i+1}]; enorm)] w_eh
    h''   = one more layer of the pattern's LAST kind over h' (its own
            attention, router, experts, shared expert and bias row)
    z_i   = rmsnorm(h''_i; the module's own ln_f) lm_head     (shared head)
    mtp   = mean over the positions that have a token after next of
            logsumexp(z_i) - z_i[t_{i+2}]
    loss  = main + coeff * mtp

The embedding and the head are the model's own: their gradients are the
sum of both losses'.  A batch of ``S + 1`` tokens gives the main loss
``S`` positions and the module ``S`` positions of which the last has no
target and no weight.

    step = make_train_step(cfg, tx, loss_override=functools.partial(
        mtp.loss_fn, cfg=cfg, coeff=0.3))

The step's counters: the expert layers' (the module's layer among them,
last), ``main_loss`` and ``mtp_loss``.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from ray_tpu.models.common import _rms_norm
from ray_tpu.models.transformer import (TransformerConfig, embed_tokens,
                                        reduce_counters, run_stack,
                                        run_stacks, with_balance_loss)


def _cross_entropy(x, norm, head, targets, eps, weight=None):
    """Mean (or ``weight``-ed mean) of the next-token cross entropy of
    ``rmsnorm(x; norm) head`` against ``targets``."""
    logits = jnp.einsum("bsd,dv->bsv", _rms_norm(x, norm, eps),
                        head).astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None],
                               axis=-1).squeeze(-1)
    if weight is None:
        return jnp.mean(logz - gold)
    return jnp.sum(weight * (logz - gold)) / jnp.sum(weight)


def loss_fn(params: Dict, batch: Dict, moe_bias=None, *,
            cfg: TransformerConfig, coeff: float, mesh=None):
    """-> (loss, counters).  batch = {"tokens": [B, S+1] int32}."""
    tokens = batch["tokens"]
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    B, S = inputs.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    eps = cfg.norm_eps
    h, counted = run_stacks(embed_tokens(params, inputs, mesh),
                            params["layers"], positions, cfg, mesh,
                            moe_bias=moe_bias)
    with jax.named_scope("head_loss"):
        main = _cross_entropy(h, params["ln_f"], params["lm_head"], targets,
                              eps)
    m = params["mtp"]
    with jax.named_scope("mtp_module"):
        joined = jnp.concatenate(
            [_rms_norm(h, m["hnorm"], eps),
             _rms_norm(embed_tokens(params, targets, mesh), m["enorm"], eps)],
            axis=-1)
        x = jnp.einsum("bse,ed->bsd", joined, m["w_eh"])
        bias = None if moe_bias is None else moe_bias[-1:]
        x, c = run_stack(x, m["layers"], cfg.layer_pattern[-1][:2],
                         positions, cfg, mesh, moe_bias=bias)
        counted.append(c)
    with jax.named_scope("mtp_loss"):
        # position i predicts t_{i+2}; the last has none
        after_next = jnp.concatenate(
            [targets[:, 1:], jnp.zeros((B, 1), targets.dtype)], axis=1)
        has_one = (jnp.arange(S) < S - 1).astype(jnp.float32)
        extra = _cross_entropy(x, m["ln_f"], params["lm_head"], after_next,
                               eps, jnp.broadcast_to(has_one[None], (B, S)))
    counters = dict(reduce_counters(counted), main_loss=main, mtp_loss=extra)
    return with_balance_loss(main + coeff * extra, counters, cfg), counters
