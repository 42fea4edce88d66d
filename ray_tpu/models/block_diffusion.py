"""Training by diffusion over blocks (BD3-LM's objective, as SDAR adapts
an autoregressive checkpoint with it), on the same layer scan as the
next-token objective.

A row of ``L`` clean tokens ``x0`` is cut into blocks of ``block``.
Each block draws ``t ~ U(t_min, 1)`` and each of its tokens is replaced
by the mask id with probability ``t`` (``noise``, what a collator
calls), giving ``xt``.  The model runs once over the ``2L`` positions
``[xt ; x0]`` with positions ``[0..L) ; [0..L)`` under the
``BlockDiffusion`` attention mask (a noisy block sees itself and the
clean blocks before it; the clean half is causal by blocks), and

    loss = 1 / (rows * L) * sum over masked i < L of
           (1 / t_block(i)) * (logsumexp(z_i) - z_i[x0_i])

with ``z`` the logits of the noisy half: no shift, the masked position
predicts its own clean token.

    step = make_train_step(cfg, tx, loss_override=functools.partial(
        block_diffusion.loss_fn, cfg=cfg, block=4))
    xt, weight = block_diffusion.noise(key, tokens, 4, mask_id)
    state, metrics = step(state, {"tokens": tokens, "noisy": xt,
                                  "weight": weight})
"""

from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp

from ray_tpu.models.common import _rms_norm, norm_weight
from ray_tpu.models.transformer import (TransformerConfig, run_layers,
                                        with_balance_loss)
from ray_tpu.ops.attention_mask import BlockDiffusion
from ray_tpu.util import tracing


@functools.partial(jax.jit, static_argnames=("block", "mask_id", "t_min"))
def _noise(key, tokens, block, mask_id, t_min):
    rows, length = tokens.shape
    k_t, k_mask = jax.random.split(key)
    t = jax.random.uniform(k_t, (rows, length // block), jnp.float32,
                           minval=t_min, maxval=1.0)
    t = jnp.repeat(t, block, axis=1)
    masked = jax.random.uniform(k_mask, (rows, length), jnp.float32) < t
    return (jnp.where(masked, mask_id, tokens).astype(tokens.dtype),
            jnp.where(masked, 1.0 / t, 0.0))


def noise(key, tokens, block: int, mask_id: int, t_min: float = 1e-3):
    """tokens [rows, L] -> (xt [rows, L], weight [rows, L] float32):
    one ``t ~ U(t_min, 1)`` a block, each token masked with probability
    ``t``; the weight is ``1 / t`` on masked positions, 0 elsewhere."""
    if tokens.shape[1] % block:
        raise ValueError(f"block {block} does not divide the row length "
                         f"{tokens.shape[1]}")
    with tracing.span("train.noise", category="train"):
        return _noise(key, tokens, block, mask_id, t_min)


def loss_fn(params: Dict, batch: Dict, cfg: TransformerConfig, block: int,
            mesh=None):
    """-> (loss, counters).  batch: ``tokens`` (x0) and ``noisy`` (xt)
    [rows, L] int32, ``weight`` [rows, L] float32 as ``noise`` returns
    them.  Counters: the expert layers' and ``masked_tokens``."""
    x0, xt, weight = batch["tokens"], batch["noisy"], batch["weight"]
    rows, length = x0.shape
    tokens = jnp.concatenate([xt, x0], axis=1)
    half = jnp.arange(length, dtype=jnp.int32)
    positions = jnp.broadcast_to(jnp.concatenate([half, half])[None],
                                 (rows, 2 * length))
    x, counters = run_layers(params, tokens, positions, cfg, mesh,
                             mask=BlockDiffusion(length, block))
    with jax.named_scope("block_diffusion_loss"):
        # only the noisy half is scored
        z = jnp.einsum("bsd,dv->bsv",
                       _rms_norm(x[:, :length],
                                 norm_weight(params["ln_f"], cfg),
                                 cfg.norm_eps),
                       params["lm_head"]).astype(jnp.float32)
        logz = jax.nn.logsumexp(z, axis=-1)
        gold = jnp.take_along_axis(z, x0[..., None], axis=-1).squeeze(-1)
        weight = weight.astype(jnp.float32)
        loss = jnp.sum(weight * (logz - gold)) / (rows * length)
        counters = dict(counters, masked_tokens=jnp.sum(
            (weight > 0).astype(jnp.float32)))
    return with_balance_loss(loss, counters, cfg), counters
