"""Required operations of a block-diffusion training step of a
sparse-expert decoder on one expert-parallel rank's share, and of its
attention calls, counted from the configuration file's sizes.  All
counts are per DATA token: a row of ``L`` data tokens runs as ``2L``
positions ``[noised ; clean]``.
"""

from __future__ import annotations


def _sizes(cfg: dict):
    return (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"])


def layer_matmul_params(cfg: dict) -> float:
    """Parameters a position meets in a layer's matrix products: q, k,
    v, o, the router, and the experts it is routed to HERE: ``top_k``
    choices of which the share ``held / experts`` is expected to fall on
    this rank's experts."""
    d, h, kv, dh = _sizes(cfg)
    attention = 2 * d * h * dh + 2 * d * kv * dh
    router = d * cfg["num_experts"]
    expert = 3 * d * cfg["moe_intermediate_size"]
    routed_here = (cfg["num_experts_per_tok"] * cfg["num_experts_held"]
                   / cfg["num_experts"])
    return attention + router + routed_here * expert


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Operations the forward and backward passes REQUIRE per data
    token: 6 per matmul parameter a position meets, for the 2 positions
    a data token runs as, in every layer; 6 per parameter of the output
    head, which only the noised half meets; and attention under the
    block-diffusion mask, which admits ``L + block`` keys per data token
    (``L**2 + L * block`` pairs a row): ``QK^T`` and ``PV`` are 4 *
    head_dim operations a pair and head forward, three times that with
    the backward.  Recomputed operations (remat) are not counted.

    Two roundings, both upwards: the clean half of the LAST layer is
    counted in full although nothing reads it but that layer's own
    attention (1/96 of the published depth's layer work, 1/12 of this
    cut's); and the experts' share is the EXPECTED load of this rank
    under a balanced router, not the drawn one (the run's
    ``moe_held_choices`` is among its facts)."""
    d, h, _, dh = _sizes(cfg)
    layers = cfg["num_hidden_layers"]
    block = cfg["block_diffusion"]["block_length"]
    matmuls = 6.0 * layer_matmul_params(cfg) * 2 * layers
    head = 6.0 * d * cfg["vocab_size"]
    attention = 3.0 * 4 * h * dh * (seq_len + block) * layers
    return matmuls + head + attention


def flash_call_cost(cfg: dict, rows: int, seq_len: int, backward: bool,
                    itemsize: int = 2) -> dict:
    """One attention call over ``rows`` rows of ``2 * seq_len``
    positions under the block-diffusion mask: the same count whatever
    implements the kernel.  Forward: ``4 * head_dim * (L**2 + L *
    block)`` operations a query head; backward twice that (four
    products, no recomputation counted).  Least HBM traffic: forward
    reads q and writes out and a float32 log-sum-exp per query head,
    reads k and v once per K/V head; the backward reads q, out, dout and
    the log-sum-exp and writes dq per query head, reads k, v and writes
    dk, dv once per K/V head."""
    _, h, kv, dh = _sizes(cfg)
    block = cfg["block_diffusion"]["block_length"]
    pairs = seq_len * seq_len + seq_len * block
    positions = 2 * seq_len
    per_tensor = positions * dh * itemsize
    if backward:
        flops = 2 * 4.0 * dh * pairs * rows * h
        nbytes = rows * (h * (4 * per_tensor + 4 * positions)
                         + kv * 4 * per_tensor)
    else:
        flops = 4.0 * dh * pairs * rows * h
        nbytes = rows * (h * (2 * per_tensor + 4 * positions)
                         + kv * 2 * per_tensor)
    return {"flops": flops, "bytes": float(nbytes)}


def flash_roofline_share(ctx: dict, kernel: str, backward: bool):
    """Percent of its roofline that the kernel whose trace events match
    ``kernel`` reached in a traced window (``ctx`` as ``run_cell`` hands
    it to a per-layer reader).  No such event, or a configuration
    without a block-diffusion section: nothing is returned."""
    from benchmarks.harness import peaks, trace_reduce
    found = trace_reduce.op_seconds(ctx["trace"], kernel)
    calls = sum(n for n, _ in found.values())
    seconds = sum(s for _, s in found.values())
    if not calls or not seconds or "block_diffusion" not in ctx["config"]:
        return None
    cost = flash_call_cost(ctx["config"], ctx["facts"]["rows"],
                           ctx["facts"]["seq_len"], backward)
    least = peaks.roofline(cost["flops"], cost["bytes"], ctx["device_kind"])
    return 100.0 * least["min_s"] * calls / seconds
