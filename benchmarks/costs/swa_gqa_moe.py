"""Required operations of a training step of a decoder whose attention
layers are of two kinds (a window, and everything before, with their own
head counts) over a leading dense layer and sparse expert layers beside
a shared expert, on one expert-parallel rank's share, and of its flash
kernels' calls, counted from the configuration file's sizes.  All counts
are of what the layers' equations require -- whatever implements them.
"""

from __future__ import annotations

from benchmarks.harness.swa_moe_weights import layer_plan


def attention_matmul_params(cfg: dict, entry: dict) -> int:
    """One layer's projections: query, key, value, the head-wise gate,
    out."""
    d, kv, dh = (cfg["hidden_size"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    h = entry["heads"]
    return d * h * dh + 2 * d * kv * dh + d * h + h * dh * d


def ffn_matmul_params(cfg: dict, entry: dict) -> float:
    """What a token meets in one layer's FFN HERE: the dense SwiGLU, or
    the router, the shared expert and its ``num_experts_per_tok``
    choices of which the share ``held / experts`` is expected on this
    rank."""
    d = cfg["hidden_size"]
    if entry["ffn"] == "dense":
        return 3 * d * cfg["intermediate_size"]
    routed_here = (cfg["num_experts_per_tok"] * cfg["num_experts_held"]
                   / cfg["num_experts"])
    return (d * cfg["num_experts"]
            + 3 * d * cfg["shared_expert_intermediate_size"]
            + routed_here * 3 * d * cfg["moe_intermediate_size"])


def attention_pairs(seq_len: int, window) -> int:
    """(query, key) pairs a row's mask allows: the causal triangle, or
    its band of ``window`` keys a query, its own among them."""
    if window is None or window >= seq_len:
        return seq_len * (seq_len + 1) // 2
    return window * (window + 1) // 2 + (seq_len - window) * window


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Operations the forward and backward passes REQUIRE per token: 6
    per matmul parameter a token meets (the projections and the gate,
    the dense SwiGLU, the router, the shared expert and the EXPECTED
    share of held experts -- not the drawn load: the run's
    ``moe_held_choices`` is among its facts -- and the head), and three
    times the forward count of the pairs each layer's mask allows a
    token, ``4 * head_dim`` operations a pair and query head.
    Recomputed operations (remat) are not counted."""
    plan = layer_plan(cfg)
    matmuls = sum(attention_matmul_params(cfg, e) + ffn_matmul_params(cfg, e)
                  for e in plan) + cfg["hidden_size"] * cfg["vocab_size"]
    pairs = sum(3.0 * 4 * cfg["head_dim"] * e["heads"]
                * attention_pairs(seq_len, e["window"]) / seq_len
                for e in plan)
    return 6.0 * matmuls + pairs


def flash_step_cost(cfg: dict, rows: int, seq_len: int, backward: bool,
                    itemsize: int = 2) -> list:
    """[{flops, bytes}] of the step's attention calls, one a layer: the
    layer's query heads of ``head_dim`` over ``num_key_value_heads`` K/V
    heads, ``4 * head_dim`` operations a pair the layer's mask allows
    and query head forward, twice that backward (four products, no
    recomputation counted).  Least HBM traffic: the forward reads q and
    writes out and a float32 log-sum-exp a query head and reads k and v
    ONCE A K/V HEAD; the backward reads q, out, dout and the log-sum-exp
    and writes dq a query head, reads k, v and writes dk, dv once a K/V
    head."""
    kv, dh = cfg["num_key_value_heads"], cfg["head_dim"]
    per_tensor = seq_len * dh * itemsize
    calls = []
    for entry in layer_plan(cfg):
        h = entry["heads"]
        forward = 4.0 * dh * attention_pairs(seq_len, entry["window"]) \
            * rows * h
        if backward:
            calls.append({"flops": 2 * forward, "bytes": float(rows * (
                h * (4 * per_tensor + 4 * seq_len) + kv * 4 * per_tensor))})
        else:
            calls.append({"flops": forward, "bytes": float(rows * (
                h * (2 * per_tensor + 4 * seq_len) + kv * 2 * per_tensor))})
    return calls


def flash_roofline_share(ctx: dict, kernel: str, backward: bool):
    """Percent of their rooflines that the step's attention calls
    reached together: the least times of the step's calls, summed, times
    the steps of the window over the device time of the kernel's events
    (the calls differ -- bands at one head count, triangles at another
    -- so it is not one call's cost times the events).  No such event,
    or another configuration: nothing."""
    from benchmarks.harness import peaks, trace_reduce
    found = trace_reduce.op_seconds(ctx["trace"], kernel)
    seconds = sum(s for _, s in found.values())
    steps = ctx["facts"].get("steps")
    if not seconds or not steps or "layer_types" not in ctx["config"]:
        return None
    least = sum(peaks.roofline(c["flops"], c["bytes"],
                               ctx["device_kind"])["min_s"]
                for c in flash_step_cost(
                    ctx["config"], ctx["facts"]["rows"],
                    ctx["facts"]["seq_len"], backward))
    devices = max(1, len(ctx["trace"]["device_ops"]))
    return 100.0 * least * steps * devices / seconds
