"""Required operations of a training step of a hybrid delta-rule /
gated-attention sparse-expert decoder on one expert-parallel rank's
share, and of its kernels' calls, counted from the configuration file's
sizes.  All counts are per token of the batch, and of what the layers'
equations require -- whatever implements them.
"""

from __future__ import annotations


def _delta(cfg: dict):
    return (cfg["linear_num_key_heads"], cfg["linear_num_value_heads"],
            cfg["linear_key_head_dim"], cfg["linear_value_head_dim"])


def layer_counts(cfg: dict):
    """(delta layers, attention layers): one attention layer closes
    every period of ``full_attention_interval``."""
    attention = cfg["num_hidden_layers"] // cfg["full_attention_interval"]
    return cfg["num_hidden_layers"] - attention, attention


def delta_matmul_params(cfg: dict) -> int:
    """The delta layer's three projections: q | k | v | z, b | a, out."""
    d, (hk, hv, dk, dv) = cfg["hidden_size"], _delta(cfg)
    return d * (2 * hk * dk + 2 * hv * dv) + d * 2 * hv + hv * dv * d


def attention_matmul_params(cfg: dict) -> int:
    """Gated attention's projections: query | gate, key, value, out."""
    d, h, kv, dh = (cfg["hidden_size"], cfg["num_attention_heads"],
                    cfg["num_key_value_heads"], cfg["head_dim"])
    return d * h * 2 * dh + 2 * d * kv * dh + h * dh * d


def expert_ffn_matmul_params(cfg: dict) -> float:
    """What a token meets in an expert layer HERE: the router, the
    shared expert with its gate, and its ``num_experts_per_tok`` choices
    of which the share ``held / experts`` is expected on this rank."""
    d = cfg["hidden_size"]
    routed_here = (cfg["num_experts_per_tok"] * cfg["num_experts_held"]
                   / cfg["num_experts"])
    return (d * cfg["num_experts"] + d
            + 3 * d * cfg["shared_expert_intermediate_size"]
            + routed_here * 3 * d * cfg["moe_intermediate_size"])


def delta_rule_flops_per_token(cfg: dict) -> float:
    """The recurrence, forward, a token and layer: four passes over a
    value head's ``[Dk, Dv]`` state (decay, read by k, rank-one write,
    read by q), two operations an element each."""
    _, hv, dk, dv = _delta(cfg)
    return 4 * 2.0 * dk * dv * hv


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Operations the forward and backward passes REQUIRE per token: 6
    per matmul parameter a token meets (both mixers' projections, the
    router, the shared expert and the EXPECTED share of held experts --
    not the drawn load: the run's ``moe_held_choices`` is among its
    facts -- and the head), three times the delta rule's forward count
    in the delta layers, and causal attention over ``head_dim`` score
    and value columns in the attention layers: ``4 * head_dim``
    operations a pair and head forward, ``seq_len / 2`` pairs a token,
    three times that with the backward.  Recomputed operations (remat)
    and the chunked form's extra products are not counted."""
    delta, attention = layer_counts(cfg)
    matmuls = (delta * delta_matmul_params(cfg)
               + attention * attention_matmul_params(cfg)
               + (delta + attention) * expert_ffn_matmul_params(cfg)
               + cfg["hidden_size"] * cfg["vocab_size"])
    rule = 3.0 * delta_rule_flops_per_token(cfg) * delta
    scores = 3.0 * 4 * cfg["head_dim"] * cfg["num_attention_heads"] \
        * (seq_len / 2) * attention
    return 6.0 * matmuls + rule + scores


def flash_call_cost(cfg: dict, rows: int, seq_len: int, backward: bool,
                    itemsize: int = 2) -> dict:
    """One causal attention call over ``rows`` rows of ``seq_len``
    positions at ``head_dim``-wide heads with grouped K/V: ``4 *
    head_dim`` operations a pair and query head over ``seq_len**2 / 2``
    pairs forward, twice that backward (four products, no recomputation
    counted).  Least HBM traffic: the forward reads q and writes out and
    a float32 log-sum-exp per query head and reads k and v ONCE A K/V
    HEAD (a group of 8 query heads); the backward reads q, out, dout and
    the log-sum-exp and writes dq per query head, reads k, v and writes
    dk, dv once a K/V head."""
    h, kv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    forward = 4.0 * dh * (seq_len * seq_len / 2) * rows * h
    per_tensor = seq_len * dh * itemsize
    if backward:
        flops = 2 * forward
        nbytes = rows * (h * (4 * per_tensor + 4 * seq_len)
                         + kv * 4 * per_tensor)
    else:
        flops = forward
        nbytes = rows * (h * (2 * per_tensor + 4 * seq_len)
                         + kv * 2 * per_tensor)
    return {"flops": flops, "bytes": float(nbytes)}


def state_pass_cost(cfg: dict, rows: int, seq_len: int, backward: bool,
                    itemsize: int = 2) -> dict:
    """One pass over the delta layers' state for ``rows`` rows: per
    value head and chunk of ``gdn_chunk`` positions the two products
    that hold the state (``V' = U - W S`` and ``S <- c S + Kd^T V'``,
    ``2 * chunk * Dk * Dv`` operations each), reading W, U and Kd and
    writing V' in the activations' type and the chunk's entering state
    ``[Dk, Dv]`` in float32.  The transpose has four such products (the
    two cotangents of each) and reads W, Kd, V', the states and the
    cotangents of V' and of the states, and writes those of W, U and Kd.
    Memory-bound: 1.07 GB forward and 2.01 GB backward at 2 rows of
    8,192 with 32 heads of 128 x 128."""
    _, hv, dk, dv = _delta(cfg)
    chunk = cfg["gdn_chunk"]
    chunks = rows * hv * (seq_len // chunk)
    product = 2.0 * chunk * dk * dv
    narrow, wide = chunk * dk * itemsize, chunk * dv * itemsize
    state = dk * dv * 4
    if backward:
        flops = 4 * product * chunks
        nbytes = chunks * (2 * narrow + 2 * wide + 2 * state
                           + 2 * narrow + wide)
    else:
        flops = 2 * product * chunks
        nbytes = chunks * (2 * narrow + 2 * wide + state)
    return {"flops": flops, "bytes": float(nbytes)}


def roofline_share(ctx: dict, kernel: str, cost):
    """Percent of its roofline that the kernel whose trace events match
    ``kernel`` reached in a traced window (``ctx`` as ``run_cell`` hands
    it to a per-layer reader); ``cost(cfg, rows, seq_len)`` counts one
    call.  No such event, or a configuration without delta layers:
    nothing is returned."""
    from benchmarks.harness import peaks, trace_reduce
    found = trace_reduce.op_seconds(ctx["trace"], kernel)
    calls = sum(n for n, _ in found.values())
    seconds = sum(s for _, s in found.values())
    if not calls or not seconds or "linear_num_value_heads" \
            not in ctx["config"]:
        return None
    one = cost(ctx["config"], ctx["facts"]["rows"], ctx["facts"]["seq_len"])
    least = peaks.roofline(one["flops"], one["bytes"], ctx["device_kind"])
    return 100.0 * least["min_s"] * calls / seconds
