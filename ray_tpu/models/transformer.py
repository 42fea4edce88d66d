"""Flagship model: a decoder-only transformer, TPU-first.

Design notes (this is the model the framework's Train library and the
graft entry exercise):
  * Pure functional jax — params are a pytree of arrays, the whole train
    step is one ``jit`` over a global ``Mesh``; XLA/GSPMD inserts all
    collectives from the shardings (no hand-written allreduce, unlike the
    reference's Train/torch DDP backend, ``python/ray/train/torch.py``).
  * Megatron-style tensor parallelism over ``tp`` (heads + FFN hidden
    sharded), data parallel over ``dp``, context parallel over ``sp``
    via ring attention (ops/ring_attention.py), sequence-parallel
    activation sharding between blocks.
  * ``lax.scan`` over stacked layer params — one compilation regardless
    of depth; optional ``jax.checkpoint`` rematerialisation that keeps
    each layer's input and the flash kernel's ``out`` and ``lse``
    (``remat_layer``) and recomputes the rest in the backward pass.
  * bf16 activations/params with f32 RMSNorm + softmax + Adam moments.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ray_tpu.ops.attention_mask import CAUSAL
from ray_tpu.ops.flash_attention import (
    RESIDUAL_NAMES as FLASH_RESIDUAL_NAMES,
    attention as flash_or_ref_attention)
from ray_tpu.ops.ring_attention import ring_attention
from ray_tpu.util import tracing


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32_000
    d_model: int = 512
    n_layers: int = 4
    n_heads: int = 8
    d_ff: int = 1408
    max_seq_len: int = 2048
    rope_theta: float = 10_000.0
    dtype: Any = jnp.bfloat16
    #: Recompute each layer in the backward pass from its input and the
    #: flash kernel's ``out`` and ``lse`` (``remat_layer``).
    remat: bool = True
    #: Use ring attention over the "sp" mesh axis when its size > 1.
    context_parallel: bool = True
    #: K/V heads (query head h reads K/V head h // group); 0: n_heads.
    n_kv_heads: int = 0
    #: 0: d_model // n_heads.
    head_dim: int = 0
    #: RMSNorm over each head of q and k before RoPE (weights
    #: ``q_norm``/``k_norm`` [head_dim], shared by the heads).
    qk_norm: bool = False
    norm_eps: float = 1e-5
    #: >0 replaces the dense FFN with a mixture of this many experts of
    #: width ``d_ff`` (models/moe.py): ``moe_top_k`` per token,
    #: renormalised over the chosen ones if ``moe_norm_topk``.  Expert
    #: weights shard over the "ep" mesh axis.
    moe_experts: int = 0
    moe_top_k: int = 2
    moe_norm_topk: bool = True
    #: Weight of the router's load-balance auxiliary in the loss
    #: (``moe.balance_loss``, mean over the layers); 0 leaves it a
    #: counter.
    moe_aux_coeff: float = 0.01
    #: The step's metrics also carry every token's experts,
    #: ``moe_choices`` [n_layers, B, S, moe_top_k] int32, so that a
    #: reference can follow the routing it checks.
    moe_report_choices: bool = False
    #: ``(first, count)``: the experts this program holds of a layer
    #: shared with other chips (None: all).  The router stays
    #: ``moe_experts`` wide; what absent experts would add is left out.
    moe_experts_held: Optional[Tuple[int, int]] = None

    def __post_init__(self):
        if not self.n_kv_heads:
            object.__setattr__(self, "n_kv_heads", self.n_heads)
        if not self.head_dim:
            object.__setattr__(self, "head_dim",
                               self.d_model // self.n_heads)
        if self.n_heads % self.n_kv_heads:
            raise ValueError(f"{self.n_heads} query heads over "
                             f"{self.n_kv_heads} K/V heads")


def init_params(rng: jax.Array, cfg: TransformerConfig) -> Dict:
    k_embed, k_layers, k_head = jax.random.split(rng, 3)
    d, h, dh, f, nl = (cfg.d_model, cfg.n_heads, cfg.head_dim, cfg.d_ff,
                       cfg.n_layers)
    kv = cfg.n_kv_heads
    init = jax.nn.initializers.normal(0.02)
    lkeys = jax.random.split(k_layers, 6)

    def stacked(key, shape):
        return init(key, (nl,) + shape, jnp.float32).astype(cfg.dtype)

    layers: Dict = {
        "ln1": jnp.ones((nl, d), jnp.float32),
        "ln2": jnp.ones((nl, d), jnp.float32),
        "wq": stacked(lkeys[0], (d, h, dh)),
        "wk": stacked(lkeys[1], (d, kv, dh)),
        "wv": stacked(lkeys[2], (d, kv, dh)),
        "wo": stacked(lkeys[3], (h, dh, d)),
    }
    if cfg.qk_norm:
        layers["q_norm"] = jnp.ones((nl, dh), jnp.float32)
        layers["k_norm"] = jnp.ones((nl, dh), jnp.float32)
    if cfg.moe_experts > 0:
        from ray_tpu.models.moe import init_moe_params
        held = cfg.moe_experts_held or (0, cfg.moe_experts)
        layers["moe"] = init_moe_params(
            jax.random.fold_in(k_layers, 8), nl, d, f,
            cfg.moe_experts, held[1], cfg.dtype)
    else:
        layers.update({
            "w1": stacked(lkeys[4], (d, f)),
            "w3": stacked(lkeys[5], (d, f)),
            "w2": stacked(jax.random.fold_in(k_layers, 7), (f, d)),
        })
    return {
        "embed": init(k_embed, (cfg.vocab_size, d), jnp.float32
                      ).astype(cfg.dtype),
        "layers": layers,
        "ln_f": jnp.ones((d,), jnp.float32),
        "lm_head": init(k_head, (d, cfg.vocab_size), jnp.float32
                        ).astype(cfg.dtype),
    }


def param_specs(cfg: TransformerConfig) -> Dict:
    """PartitionSpecs: Megatron TP on heads/FFN-hidden, vocab on
    lm_head; MoE expert weights shard over "ep"."""
    layers: Dict = {
        "ln1": P(None, None),
        "ln2": P(None, None),
        "wq": P(None, None, "tp", None),
        "wk": P(None, None, "tp", None),
        "wv": P(None, None, "tp", None),
        "wo": P(None, "tp", None, None),
    }
    if cfg.qk_norm:
        layers["q_norm"] = P(None, None)
        layers["k_norm"] = P(None, None)
    if cfg.moe_experts > 0:
        from ray_tpu.models.moe import moe_param_specs
        layers["moe"] = moe_param_specs()
    else:
        layers.update({
            "w1": P(None, None, "tp"),
            "w3": P(None, None, "tp"),
            "w2": P(None, "tp", None),
        })
    return {
        "embed": P(None, "tp"),
        "layers": layers,
        "ln_f": P(None),
        "lm_head": P(None, "tp"),
    }


def batch_spec() -> P:
    return P("dp", "sp")


def _rms_norm(x, w, eps):
    xf = x.astype(jnp.float32)
    norm = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (norm * w).astype(x.dtype)


def _rope(x, positions, theta):
    # x: [B, S, H, D]; rotate pairs.
    d = x.shape[-1]
    half = d // 2
    freqs = jnp.exp(-jnp.log(theta) *
                    jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B, S, half]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1).astype(x.dtype)


def _attention_core(q, k, v, mesh, cfg: TransformerConfig, mask=CAUSAL):
    if (cfg.context_parallel and mesh is not None and
            mesh.shape.get("sp", 1) > 1):
        if mask != CAUSAL or cfg.n_kv_heads != cfg.n_heads:
            raise ValueError("ring attention is causal and multi-head only")
        fn = jax.shard_map(
            functools.partial(ring_attention, axis_name="sp", causal=True),
            mesh=mesh,
            in_specs=(P("dp", "sp", "tp", None),) * 3,
            out_specs=P("dp", "sp", "tp", None),
            check_vma=False)
        return fn(q, k, v)
    return flash_or_ref_attention(q, k, v, mask=mask)


def _moe_block(h, lp, cfg: TransformerConfig, mesh):
    """The expert layer on [B, S, D] -> (y, what the layer counted)."""
    from ray_tpu.models import moe
    if mesh is not None and mesh.shape.get("ep", 1) > 1:
        if cfg.moe_experts_held is not None:
            raise ValueError("moe_experts_held is one chip's share; an "
                             "ep mesh shares the experts itself")
        y, stats = moe.moe_ffn_sharded(h, lp, cfg.moe_top_k,
                                       cfg.moe_norm_topk, mesh)
    else:
        y, stats = moe.moe_ffn(h, lp, cfg.moe_top_k, cfg.moe_norm_topk,
                               held=cfg.moe_experts_held)
    counted = moe.counters(stats)
    if cfg.moe_report_choices:
        counted["moe_choices"] = stats["choices"]
    return y, counted


def apply_layer(x, lp, positions, cfg: TransformerConfig, mesh=None,
                mask=CAUSAL):
    """One transformer block on [B, S, D] activations with this
    layer's params ``lp``; returns (x, what the layer counted: nothing
    for a dense one).  Shared by the scan forward, the block-diffusion
    objective and the pipeline-parallel stage executor."""
    # The named scopes here and in loss_fn / train_step are metadata
    # only: stable names for a device trace to group time by.
    eps = cfg.norm_eps
    with jax.named_scope("attention"):
        h = _rms_norm(x, lp["ln1"], eps)
        q = jnp.einsum("bsd,dhk->bshk", h, lp["wq"])
        k = jnp.einsum("bsd,dhk->bshk", h, lp["wk"])
        v = jnp.einsum("bsd,dhk->bshk", h, lp["wv"])
        if cfg.qk_norm:
            q = _rms_norm(q, lp["q_norm"], eps)
            k = _rms_norm(k, lp["k_norm"], eps)
        q = _rope(q, positions, cfg.rope_theta)
        k = _rope(k, positions, cfg.rope_theta)
        o = _attention_core(q, k, v, mesh, cfg, mask)
        x = x + jnp.einsum("bshk,hkd->bsd", o, lp["wo"])
    counted = {}
    with jax.named_scope("ffn"):
        h = _rms_norm(x, lp["ln2"], eps)
        if cfg.moe_experts > 0:
            y, counted = _moe_block(h, lp["moe"], cfg, mesh)
            x = x + y
        else:
            gate = jax.nn.silu(jnp.einsum("bsd,df->bsf", h, lp["w1"]))
            up = jnp.einsum("bsd,df->bsf", h, lp["w3"])
            x = x + jnp.einsum("bsf,fd->bsd", gate * up, lp["w2"])
    if mesh is not None:
        x = jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, P("dp", "sp", None)))
    return x, counted


def remat_layer(layer, cfg: TransformerConfig):
    """``layer`` as a scan over the stacked layers runs it: under
    ``cfg.remat`` its backward pass recomputes the layer from its input,
    all but the flash kernel's ``out`` and ``lse``, which are kept (the
    kernel's call is the dearest thing in a layer per byte it leaves,
    and its backward kernel reads just these two).  The jnp and ring
    attention paths make no such names and are recomputed whole."""
    if not cfg.remat:
        return layer
    return jax.checkpoint(
        layer, policy=jax.checkpoint_policies.save_only_these_names(
            *FLASH_RESIDUAL_NAMES))


def run_layers(params: Dict, tokens: jax.Array, positions: jax.Array,
               cfg: TransformerConfig, mesh=None, mask=CAUSAL):
    """Embedding and the scan over the stacked layers: tokens [B, S]
    -> (x [B, S, D] before the final norm, what the layers counted:
    scalars as means over the layers, anything else stacked by layer)."""
    x = jnp.take(params["embed"], tokens, axis=0)     # [B, S, D]
    if mesh is not None:
        x = jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, P("dp", "sp", None)))

    def layer(x, lp):
        return apply_layer(x, lp, positions, cfg, mesh, mask)

    layer_fn = remat_layer(layer, cfg)
    x, counted = jax.lax.scan(lambda x, lp: layer_fn(x, lp), x,
                              params["layers"])
    return x, {k: jnp.mean(v) if v.ndim == 1 else v
               for k, v in counted.items()}


def with_balance_loss(loss, counters: Dict, cfg: TransformerConfig):
    """The objective's loss plus the router's load-balance auxiliary,
    where the model has one."""
    if cfg.moe_experts > 0 and cfg.moe_aux_coeff > 0:
        loss = loss + cfg.moe_aux_coeff * counters["moe_balance_loss"]
    return loss


def forward(params: Dict, tokens: jax.Array, cfg: TransformerConfig,
            mesh=None) -> jax.Array:
    """tokens [B, S] int32 -> logits [B, S, V]."""
    return forward_with_counters(params, tokens, cfg, mesh)[0]


def forward_with_counters(params: Dict, tokens: jax.Array,
                          cfg: TransformerConfig, mesh=None):
    """Like :func:`forward` but also returns the expert layers'
    counters (nothing for dense models)."""
    B, S = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    x, counters = run_layers(params, tokens, positions, cfg, mesh)
    with jax.named_scope("head_loss"):
        x = _rms_norm(x, params["ln_f"], cfg.norm_eps)
        logits = jnp.einsum("bsd,dv->bsv", x, params["lm_head"])
    return logits, counters


def loss_and_counters(params: Dict, batch: Dict, cfg: TransformerConfig,
                      mesh=None):
    """Next-token cross entropy (plus the router's load-balance
    auxiliary) and the expert layers' counters.
    batch = {"tokens": [B, S+1] int32}."""
    tokens = batch["tokens"]
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    logits, counters = forward_with_counters(params, inputs, cfg, mesh)
    with jax.named_scope("head_loss"):
        logits = logits.astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, targets[..., None],
                                   axis=-1).squeeze(-1)
        loss = jnp.mean(logz - gold)
    return with_balance_loss(loss, counters, cfg), counters


def loss_fn(params: Dict, batch: Dict, cfg: TransformerConfig,
            mesh=None) -> jax.Array:
    return loss_and_counters(params, batch, cfg, mesh)[0]


# ---------------------------------------------------------------------------
# Train state + step factory (used by ray_tpu.train and the graft entry).
# ---------------------------------------------------------------------------

def make_train_state(rng, cfg: TransformerConfig, mesh=None,
                     learning_rate: float = 3e-4,
                     specs_override: Optional[Dict] = None):
    import optax
    tx = optax.adamw(learning_rate, b1=0.9, b2=0.95, weight_decay=0.1)
    params = init_params(rng, cfg)
    opt_state = tx.init(params)
    state = {"params": params, "opt": opt_state,
             "step": jnp.zeros((), jnp.int32)}
    if mesh is not None:
        specs = specs_override or param_specs(cfg)
        state_specs = {
            "params": specs,
            "opt": jax.tree.map(
                lambda _: P(), opt_state,
                is_leaf=lambda x: isinstance(x, jnp.ndarray)),
            "step": P(),
        }
        # Adam moments mirror the param tree's specs.
        state_specs["opt"] = _opt_specs(opt_state, specs)
        state = jax.device_put(
            state, jax.tree.map(
                lambda s: NamedSharding(mesh, s), state_specs,
                is_leaf=lambda x: isinstance(x, P)))
    return state, tx


def _opt_specs(opt_state, param_spec_tree):
    """Mirror param specs onto the Adam moment trees, P() elsewhere."""
    def one(entry):
        if hasattr(entry, "mu") and hasattr(entry, "nu"):
            return type(entry)(count=P(), mu=param_spec_tree,
                               nu=param_spec_tree)
        return jax.tree.map(lambda _: P(), entry)
    return tuple(one(e) for e in opt_state)


def make_train_step(cfg: TransformerConfig, tx, mesh=None,
                    loss_override=None):
    """``loss_override(params, batch)`` substitutes the next-token loss
    (the pipeline-parallel schedule; the block-diffusion objective,
    ``models/block_diffusion.py``).  It returns the loss, or the loss
    and a dict of counters that join the step's ``metrics``."""
    def train_step(state, batch):
        compute = loss_override or (
            lambda p, b: loss_and_counters(p, b, cfg, mesh))

        def objective(p):
            out = compute(p, batch)
            return out if isinstance(out, tuple) else (out, {})

        (loss, counters), grads = jax.value_and_grad(
            objective, has_aux=True)(state["params"])
        with jax.named_scope("optimizer"):
            updates, new_opt = tx.update(grads, state["opt"],
                                         state["params"])
            new_params = jax.tree.map(
                lambda p, u: (p.astype(jnp.float32) + u).astype(p.dtype),
                state["params"], updates)
        new_state = {"params": new_params, "opt": new_opt,
                     "step": state["step"] + 1}
        metrics = {"loss": loss, "grad_norm": optax_global_norm(grads),
                   **counters}
        return new_state, metrics

    donate = (0,)
    return _TracedStep(jax.jit(train_step, donate_argnums=donate))


class _TracedStep:
    """The jitted ``train_step``, each call under a ``train.model_step``
    span: the host's dispatch of the step (argument flattening, cache
    lookup, donation, launch — it returns before the device finishes),
    i.e. the train worker's own cost per step.  Everything else of the
    jitted function (``.lower``, ``.trace``, ...) is reached through."""

    def __init__(self, jitted):
        self._jitted = jitted

    def __call__(self, state, batch):
        with tracing.span("train.model_step", category="train"):
            return self._jitted(state, batch)

    def __getattr__(self, name):
        return getattr(self._jitted, name)


def optax_global_norm(tree) -> jax.Array:
    leaves = jax.tree.leaves(tree)
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                        for x in leaves))
