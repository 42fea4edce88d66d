"""Required operations of a training step of a decoder of Kimi Delta
Attention and latent attention layers over dense and expert FFNs, on one
expert-parallel rank's share, and of its KDA rule's calls, counted from
the configuration file's sizes.  All counts are of what the layers'
equations require -- whatever implements them: each product of the
chunked rule once, at its mathematical size, whatever precision the
kernels compute it in, and nothing recomputed.
"""

from __future__ import annotations

from benchmarks.harness.kda_weights import layer_plan


def mixer_matmul_params(cfg: dict, entry: dict) -> int:
    """One mixer's projections: KDA's q | k | v, gate, beta and output
    gate in and its output; or latent attention's direct query, the
    key/value latent, its up-projection and the output."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    if entry["mixer"] == "kda":
        inner = h * cfg["head_dim"]
        return d * (4 * inner + 2 * h) + inner * d
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    rkv = cfg["kv_lora_rank"]
    return d * h * (dn + dr) + d * (rkv + dr) + rkv * h * (dn + dv) \
        + h * dv * d


def ffn_matmul_params(cfg: dict, entry: dict) -> float:
    """The dense SwiGLU; or what a token meets in an expert layer HERE:
    the router, the shared expert and its ``num_experts_per_tok`` choices
    of which the share ``held / experts`` is expected on this rank."""
    d = cfg["hidden_size"]
    if entry["ffn"] == "dense":
        return 3 * d * cfg["intermediate_size"]
    routed_here = (cfg["num_experts_per_tok"] * cfg["num_experts_held"]
                   / cfg["num_experts"])
    return d * cfg["num_experts"] + 3 * d * (
        cfg["moe_shared_expert_intermediate_size"]
        + routed_here * cfg["moe_intermediate_size"])


def rule_flops_per_token(cfg: dict) -> float:
    """The chunked rule's products a token, forward, at the
    configuration's chunk ``C``, a head's: the decayed pairs ``A`` of
    the chunk's keys and those of its queries against its keys (``2 C
    Dk`` each), the inverse ``(I + A)^-1`` of a unit lower triangular
    ``C x C`` (``C^3 / 3`` multiply-adds a chunk), its products with the
    decayed keys and the values (``2 C Dk``, ``2 C Dv``), the pairs'
    product with the corrected values (``2 C Dv``), and the three that
    meet the state: the corrected values' ``W S``, the output's ``(Q
    e^G) S`` and the update by the decayed keys (``2 Dk Dv`` each)."""
    c, dk = cfg["chunk_size"], cfg["head_dim"]
    dv = dk
    head = 2.0 * c * (3 * dk + 2 * dv) + 2.0 * c * c / 3 + 6.0 * dk * dv
    return cfg["num_attention_heads"] * head


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Operations the forward and backward passes REQUIRE per token: 6
    per matmul parameter a token meets (the mixers' projections, the FFNs
    -- the expert layers' EXPECTED share, not the drawn load -- and the
    head), three times the forward count of the rule
    (``rule_flops_per_token``) in every KDA layer, and causal attention
    over ``dn + dr`` score and ``dv`` value columns in every MLA layer,
    ``2 (dn + dr + dv)`` operations a pair and head forward, three times
    that with the backward.  Recomputed operations (remat) are not
    counted."""
    plan = layer_plan(cfg)
    matmuls = sum(mixer_matmul_params(cfg, e) + ffn_matmul_params(cfg, e)
                  for e in plan) + cfg["hidden_size"] * cfg["vocab_size"]
    mixers = [e["mixer"] for e in plan]
    rules = 3.0 * rule_flops_per_token(cfg) * mixers.count("kda")
    pairs = 3.0 * 2 * (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
                       + cfg["v_head_dim"]) * cfg["num_attention_heads"] \
        * (seq_len + 1) / 2 * mixers.count("mla")
    return 6.0 * matmuls + rules + pairs


def rule_call_cost(cfg: dict, rows: int, seq_len: int, backward: bool,
                   itemsize: int = 2) -> dict:
    """{flops, bytes} of one call of the rule over ``rows`` rows: the
    forward's ``rule_flops_per_token`` a token, twice that backward; the
    least HBM traffic: forward, q, k, v read in the activations' type,
    ``g`` and beta in float32, ``o`` written and the state entering
    every fourth chunk in float32; backward, those read with ``o``'s
    cotangent and the five cotangents written (``g``'s and beta's in
    float32)."""
    tokens = rows * seq_len
    h, dk = cfg["num_attention_heads"], cfg["head_dim"]
    qkv, g, beta, o = 3 * h * dk * itemsize, h * dk * 4, h * 4, \
        h * dk * itemsize
    states = rows * h * (seq_len // (4 * cfg["chunk_size"])) * dk * dk * 4
    flops = rule_flops_per_token(cfg) * tokens
    if backward:
        flops = 2 * flops
        nbytes = tokens * (2 * (qkv + g + beta) + o) + states
    else:
        nbytes = tokens * (qkv + g + beta + o) + states
    return {"flops": flops, "bytes": float(nbytes)}


def rule_roofline_share(ctx: dict, kernel: str, backward: bool):
    """Percent of its roofline that the rule's kernel whose trace events
    match ``kernel`` reached: one call's least time times the calls over
    their device time.  No such event, or another configuration:
    nothing."""
    from benchmarks.harness import peaks, trace_reduce
    found = trace_reduce.op_seconds(ctx["trace"], kernel)
    calls = sum(n for n, _ in found.values())
    seconds = sum(s for _, s in found.values())
    if not calls or not seconds or "kda_lower_bound" not in ctx["config"]:
        return None
    one = rule_call_cost(ctx["config"], ctx["facts"]["rows"],
                         ctx["facts"]["seq_len"], backward)
    least = peaks.roofline(one["flops"], one["bytes"], ctx["device_kind"])
    return 100.0 * least["min_s"] * calls / seconds
