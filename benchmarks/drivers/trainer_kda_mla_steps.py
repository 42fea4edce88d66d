"""Driver ``trainer_kda_mla_steps``: a training step of a decoder of Kimi
Delta Attention and latent attention layers over dense and expert FFNs
-- SwiGLU experts with a sigmoid router limited to the best groups of
experts and its correction bias, beside a shared expert (one
expert-parallel rank's share) -- through
``ray_tpu.train.Trainer(backend="jax", num_workers=1, use_tpu=True)``
and ``make_train_step`` with the next-token loss.

The window, the run's result, the reference it follows and the numbers
it compares are ``expert_share_steps``'; what is this configuration's is
here: the program's configuration from the file's keys, the counters'
names and KDA's rule alone on the seed's probe at the step's shape
(``rule_probe``): a gap of norms cannot see what the state is kept in,
nor a decay a head for one a channel.
"""

from __future__ import annotations

import sys

import numpy as np

from benchmarks.drivers import expert_share_steps as share
from benchmarks.harness import kda_weights as WEIGHTS

COUNTERS = ("moe_held_choices", "moe_layer_held_max", "moe_load_cv",
            "moe_expert_load_max", "moe_dropped_choices", "moe_balance_loss",
            "moe_bias_abs_max", "kda_fallback_passes", "kda_decay_mean")
FALLBACK = "kda_fallback_passes"
MEANS = ("kda_decay_mean",)
EXPERTS = ("experts_held_first", "num_experts_held", "num_experts")


def model_kwargs(config: dict, seq_len: int) -> dict:
    """The configuration file's keys -> the program's TransformerConfig
    (``kda`` and ``mla`` as the keywords of their configurations)."""
    if not config["linear_silu"] or config["q_lora_rank"] is not None \
            or config.get("tie_word_embeddings", False) \
            or config["use_kda_lora"] \
            or not config["kda_safe_gate"] or config["use_mla_nope"] \
            or config["score_function"] != "sigmoid" \
            or config["scale_router_input"] or config["use_nGPT"] \
            or config["value_norm"] or config["up_proj_norm"] \
            or config["num_kv_heads_for_linear_attn"] not in (
                0, config["num_attention_heads"]) \
            or config["group_norm_size"] != 1 \
            or config["gated_attention_proj_granularity_type"] != "head_wise" \
            or any(config["expert_swiglu_limit_list"][i]
                   or config["share_expert_swiglu_limit_list"][i]
                   for i in config["kept_layers"]):
        raise ValueError("the configuration is not the one this driver "
                         "was written for")
    return dict(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"], head_dim=config["head_dim"],
        d_ff=config["intermediate_size"], max_seq_len=seq_len,
        remat=config["remat"], norm_eps=config["rms_norm_eps"],
        rope_theta=float(config["rope_theta"]),
        layer_pattern=tuple(WEIGHTS.pattern_of(config)),
        kda=dict(num_heads=config["num_attention_heads"],
                 head_dim=config["head_dim"],
                 conv_kernel=config["short_conv_kernel_size"],
                 chunk=config["chunk_size"],
                 lower=float(config["kda_lower_bound"])),
        mla=dict(q_lora_rank=None, kv_lora_rank=config["kv_lora_rank"],
                 qk_nope_head_dim=config["qk_nope_head_dim"],
                 qk_rope_head_dim=config["qk_rope_head_dim"],
                 v_head_dim=config["v_head_dim"],
                 rope_interleave=config.get("rope_interleave", False),
                 qk_norm=config["use_qk_norm"]),
        moe_experts=config["num_experts"],
        moe_top_k=config["num_experts_per_tok"],
        moe_norm_topk=config["norm_topk_prob"],
        moe_d_ff=config["moe_intermediate_size"],
        moe_scoring="sigmoid",
        moe_route_scale=config["routed_scaling_factor"],
        moe_n_group=config["n_group"], moe_topk_group=config["topk_group"],
        moe_bias_rate=config["router_bias_update_rate"],
        moe_shared_width=config["moe_shared_expert_intermediate_size"],
        moe_experts_held=(config["experts_held_first"],
                          config["num_experts_held"]),
        moe_aux_coeff=config["router_aux_loss_coef"],
        moe_alike_tail=config["dispatch_alike_tail"],
        # the checked steps hand their routing to the reference
        moe_report_choices=True)


def transformer_config(kwargs: dict, dtype):
    """The program's configuration from ``model_kwargs``' plain data."""
    from ray_tpu.models.kda import KDAConfig
    from ray_tpu.models.mla import MLAConfig
    from ray_tpu.models.transformer import TransformerConfig
    return TransformerConfig(dtype=dtype, **dict(
        kwargs, kda=KDAConfig(**kwargs["kda"]),
        mla=MLAConfig(**kwargs["mla"])))


def rule_probe(config: dict, seed: int, rows: int, length: int):
    """The program's rule alone, as the step calls it (operands in the
    configuration's type, both kernels on a TPU), and its ``jax.vjp``
    under the probe's cotangent, on the seed's probe of ``rows`` x
    ``length`` positions -> (the reference's ``PROBE_PARTS`` on the
    host, ``kda_fallback_passes`` of the probe's own call)."""
    import jax

    from ray_tpu.ops import kda
    dtype = jax.numpy.dtype(config["dtype"])
    chunk = min(config["chunk_size"], length)

    @jax.jit
    def run(q, k, v, g, beta, do):
        o, vjp = jax.vjp(lambda q, k, v, g, beta: kda.kda_rule(
            q, k, v, g, beta, chunk=chunk), q.astype(dtype), k.astype(dtype),
            v.astype(dtype), g, beta)
        return (o, *vjp(do.astype(dtype)))

    reference = share.reference(config)
    out = run(*reference.rule_probe_inputs(seed, config, rows, length))
    return ({name: np.asarray(x, np.float32)
             for name, x in zip(reference.PROBE_PARTS, out)},
            kda.fallback_passes())


def run(cell: dict, config: dict, traffic: dict, seed: int, seconds: float,
        trace_dir) -> dict:
    # First, so that a program without the KDA kind fails here, in
    # seconds, before any runtime is started.
    from ray_tpu.models.kda import KDAConfig  # noqa: F401
    return share.run(sys.modules[__name__], cell, config, traffic, seed,
                     seconds, trace_dir)


def follow_reference(cell: dict, config: dict, seed: int, batches,
                     **how) -> dict:
    """The configuration's plain reference over the first steps.
    ``how``: ``choices`` (the program's experts, to be followed and
    checked) and the controls' ``precision``, ``decay``, ``state``,
    ``groups``, ``gate``, ``learning_rate``."""
    return share.follow_reference(sys.modules[__name__], cell, config,
                                  seed, batches, **how)


def rule_numbers(config: dict, seed: int, program_probe: dict,
                 **how) -> dict:
    """``kda_rule_gap`` and ``kda_rule_grad_gap``: the program's probe
    against the reference's recurrence on the same inputs (``how``: the
    controls' ``decay``, ``state``)."""
    return share.rule_numbers(config, seed, program_probe, **how)


def check(cell: dict, config: dict, seed: int, result: dict) -> dict:
    """``expert_share_steps.check``: the reference holds each choice to
    its own ``score + bias`` within its own kept groups."""
    return share.check(sys.modules[__name__], cell, config, seed, result)
