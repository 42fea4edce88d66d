"""Required operations of a training step of a decoder whose layers are
each one sublayer -- Mamba-2 mixers, grouped-query attention without
positional encoding, and expert layers of squared-ReLU experts in a
latent beside a full-width shared expert -- on one expert-parallel
rank's share, and of its state-space rule's calls, counted from the
configuration file's sizes.  All counts are of what the layers'
equations require -- whatever implements them.
"""

from __future__ import annotations

from benchmarks.harness.mamba2_moe_weights import layer_plan


def mixer_matmul_params(cfg: dict, entry: dict) -> int:
    """One mixer's projections: Mamba-2's ``z | xBC | dt`` in and out,
    or attention's query, key, value and out."""
    d = cfg["hidden_size"]
    if entry["mixer"] == "mamba2":
        inner = cfg["mamba_num_heads"] * cfg["mamba_head_dim"]
        conv = inner + 2 * cfg["n_groups"] * cfg["ssm_state_size"]
        return d * (inner + conv + cfg["mamba_num_heads"]) + inner * d
    hq, kv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    return 2 * d * hq * dh + 2 * d * kv * dh


def expert_matmul_params(cfg: dict) -> float:
    """What a token meets in one expert layer HERE: the router, the
    latent pair, the shared expert (two matrices) and its
    ``num_experts_per_tok`` choices of which the share ``held /
    experts`` is expected on this rank (two matrices of the latent's
    width each)."""
    d, lat = cfg["hidden_size"], cfg["moe_latent_size"]
    routed_here = (cfg["num_experts_per_tok"] * cfg["n_routed_experts_held"]
                   / cfg["n_routed_experts"])
    return (d * cfg["n_routed_experts"] + 2 * d * lat
            + 2 * d * cfg["moe_shared_expert_intermediate_size"]
            + routed_here * 2 * lat * cfg["moe_intermediate_size"])


def rule_flops_per_token(cfg: dict) -> float:
    """The chunked state-space rule's products a token, forward, at the
    configuration's chunk ``C``: ``C B^T`` once a group (``2 C N`` a
    token), and a head's masked product with ``X`` (``2 C P``), ``C S``
    and the state's update (``2 N P`` each)."""
    c, n = cfg["chunk_size"], cfg["ssm_state_size"]
    h, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    return 2.0 * c * n * cfg["n_groups"] + h * (2.0 * c * p + 4.0 * n * p)


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Operations the forward and backward passes REQUIRE per token: 6
    per matmul parameter a token meets (the mixers' projections, the
    expert layers' EXPECTED share -- not the drawn load -- and the head),
    three times the forward count of the rule (``rule_flops_per_token``)
    and of the causal pairs of each attention layer, ``4 * head_dim``
    operations a pair and query head.  Recomputed operations (remat) are
    not counted."""
    plan = layer_plan(cfg)
    matmuls = sum(mixer_matmul_params(cfg, e)
                  + (expert_matmul_params(cfg) if e["ffn"] == "moe" else 0)
                  for e in plan) + cfg["hidden_size"] * cfg["vocab_size"]
    mixers = [e["mixer"] for e in plan]
    rules = 3.0 * rule_flops_per_token(cfg) * mixers.count("mamba2")
    pairs = 3.0 * 4 * cfg["head_dim"] * cfg["num_attention_heads"] \
        * (seq_len + 1) / 2 * mixers.count("mha")
    return 6.0 * matmuls + rules + pairs


def rule_call_cost(cfg: dict, rows: int, seq_len: int, backward: bool,
                   itemsize: int = 2) -> dict:
    """{flops, bytes} of one call of the rule over ``rows`` rows: the
    forward's ``rule_flops_per_token``, twice that backward; the least
    HBM traffic: forward, ``X``, ``B``, ``C`` read in the activations'
    type and the decays' running sum in float32, ``y`` written;
    backward, those read with ``y``'s cotangent and the five cotangents
    written (the decays' in float32)."""
    tokens = rows * seq_len
    h, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    gn = cfg["n_groups"] * cfg["ssm_state_size"]
    x, bc, g = h * p * itemsize, 2 * gn * itemsize, h * 4
    flops = rule_flops_per_token(cfg) * tokens
    if backward:
        return {"flops": 2 * flops, "bytes": float(tokens * 2 * (
            2 * x + bc + g))}
    return {"flops": flops, "bytes": float(tokens * (2 * x + bc + g))}


def rule_roofline_share(ctx: dict, kernel: str, backward: bool):
    """Percent of its roofline that the rule's kernel whose trace events
    match ``kernel`` reached: one call's least time times the calls over
    their device time.  No such event, or another configuration:
    nothing."""
    from benchmarks.harness import peaks, trace_reduce
    found = trace_reduce.op_seconds(ctx["trace"], kernel)
    calls = sum(n for n, _ in found.values())
    seconds = sum(s for _, s in found.values())
    if not calls or not seconds or "ssm_state_size" not in ctx["config"]:
        return None
    one = rule_call_cost(ctx["config"], ctx["facts"]["rows"],
                         ctx["facts"]["seq_len"], backward)
    least = peaks.roofline(one["flops"], one["bytes"], ctx["device_kind"])
    return 100.0 * least["min_s"] * calls / seconds
