"""Required operations of a training step of a latent-attention
sparse-expert decoder with a multi-token-prediction module on one
expert-parallel rank's share, and of its attention calls, counted from
the configuration file's sizes.  All counts are per token of the batch.
"""

from __future__ import annotations


def _heads(cfg: dict):
    return (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["v_head_dim"])


def attention_matmul_params(cfg: dict) -> int:
    """Latent attention's five projections: the two down-projections,
    the two up-projections by heads, and the output."""
    d, (h, dn, dr, dv) = cfg["hidden_size"], _heads(cfg)
    rq, rkv = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    return (d * rq + rq * h * (dn + dr) + d * (rkv + dr)
            + rkv * h * (dn + dv) + h * dv * d)


def expert_ffn_matmul_params(cfg: dict) -> float:
    """What a token meets in an expert layer's FFN HERE: the router, the
    shared expert, and its ``num_experts_per_tok`` choices of which the
    share ``held / experts`` is expected on this rank's experts."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    routed_here = (cfg["num_experts_per_tok"] * cfg["n_routed_experts_held"]
                   / cfg["n_routed_experts"])
    return (d * cfg["n_routed_experts"]
            + 3 * d * f * (cfg["n_shared_experts"] + routed_here))


def layer_counts(cfg: dict):
    """(dense-FFN layers, expert layers) a token passes: the stack's,
    and each multi-token-prediction module is one more expert layer."""
    dense = cfg["first_k_dense_replace"]
    return dense, (cfg["num_hidden_layers"] - dense
                   + cfg["num_nextn_predict_layers"])


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Operations the forward and backward passes REQUIRE per token: 6
    per matmul parameter a token meets -- latent attention's projections
    in every layer, the dense FFN or the expert layer's router, shared
    expert and EXPECTED share of held experts (not the drawn load: the
    run's ``moe_held_choices`` is among its facts), the module's
    ``[2d, d]`` projection, and BOTH heads (the module's is the same
    matrix, met again) -- and causal attention over ``dn + dr`` score
    columns and ``dv`` value columns: ``2 * (dn + dr + dv)`` operations
    a pair and head forward, ``seq_len / 2`` pairs a token, three times
    that with the backward.  Recomputed operations (remat) are not
    counted.  One rounding, upwards: the module's last position a row
    has no target and is counted (1 / seq_len of its work)."""
    d, (h, dn, dr, dv) = cfg["hidden_size"], _heads(cfg)
    dense, sparse = layer_counts(cfg)
    modules = cfg["num_nextn_predict_layers"]
    matmuls = ((dense + sparse) * attention_matmul_params(cfg)
               + dense * 3 * d * cfg["intermediate_size"]
               + sparse * expert_ffn_matmul_params(cfg)
               + modules * 2 * d * d
               + (1 + modules) * d * cfg["vocab_size"])
    attention = 3.0 * 2 * (dn + dr + dv) * h * (seq_len / 2) \
        * (dense + sparse)
    return 6.0 * matmuls + attention


def flash_call_cost(cfg: dict, rows: int, seq_len: int, backward: bool,
                    itemsize: int = 2) -> dict:
    """One causal attention call over ``rows`` rows of ``seq_len``
    positions: the same count whatever implements the kernel.  Forward:
    ``2 * (dn + dr + dv)`` operations a pair and head over ``seq_len**2
    / 2`` pairs; backward twice that (four products, no recomputation
    counted).  Least HBM traffic: the forward reads q (``dn + dr``
    columns), k (``dn``) and v per head and the rotary key (``dr``) ONCE
    A ROW, writes out (``dv``) and a float32 log-sum-exp per head; the
    backward reads q, k, v, out, dout and the log-sum-exp and writes dq,
    dk, dv per head, reads the rotary key and writes its gradient once a
    row."""
    h, dn, dr, dv = _heads(cfg)
    pairs = seq_len * seq_len / 2
    forward = 2.0 * (dn + dr + dv) * pairs * rows * h
    col = seq_len * itemsize                     # bytes of one column
    if backward:
        flops = 2 * forward
        per_head = col * (2 * (dn + dr) + 2 * dn + 2 * dv + 2 * dv) \
            + 4 * seq_len
        per_row = col * 2 * dr
    else:
        flops = forward
        per_head = col * ((dn + dr) + dn + dv + dv) + 4 * seq_len
        per_row = col * dr
    return {"flops": flops, "bytes": float(rows * (h * per_head + per_row))}


def flash_roofline_share(ctx: dict, kernel: str, backward: bool):
    """Percent of its roofline that the kernel whose trace events match
    ``kernel`` reached in a traced window (``ctx`` as ``run_cell`` hands
    it to a per-layer reader).  No such event, or a configuration
    without latent attention: nothing is returned."""
    from benchmarks.harness import peaks, trace_reduce
    found = trace_reduce.op_seconds(ctx["trace"], kernel)
    calls = sum(n for n, _ in found.values())
    seconds = sum(s for _, s in found.values())
    if not calls or not seconds or "kv_lora_rank" not in ctx["config"]:
        return None
    cost = flash_call_cost(ctx["config"], ctx["facts"]["rows"],
                           ctx["facts"]["seq_len"], backward)
    least = peaks.roofline(cost["flops"], cost["bytes"], ctx["device_kind"])
    return 100.0 * least["min_s"] * calls / seconds
