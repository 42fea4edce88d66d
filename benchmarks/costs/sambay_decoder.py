"""Required operations of a training step of a slice of a decoder-hybrid-
decoder (Mamba, differential attention under a window or over everything
before, a Gated Memory Unit, differential cross attention), and of its
kernels' calls, counted from the configuration file's sizes.  All counts
are of what the layers' equations require -- whatever implements them.
"""

from __future__ import annotations

from benchmarks.harness.sambay_weights import layer_plan


def _sizes(cfg: dict):
    d, h, kv = (cfg["hidden_size"], cfg["num_attention_heads"],
                cfg["num_key_value_heads"])
    return d, h, kv, d // h


def mixer_matmul_params(cfg: dict, entry: dict) -> int:
    """The matrices of one layer's mixer (biases, norms, taps, ``A``,
    ``D`` and the lambdas are no products)."""
    d, h, kv, dh = _sizes(cfg)
    e, n, r = (cfg["mamba_d_inner"], cfg["mamba_d_state"],
               cfg["mamba_dt_rank"])
    if entry["kind"] == "mamba":
        return d * 2 * e + e * (r + 2 * n) + r * e + e * d
    if entry["kind"] == "gmu":
        return 2 * d * e
    own_kv = 0 if entry["reads"] else 2 * d * kv * dh
    return d * h * dh + own_kv + h * dh * d


def attention_pairs(seq_len: int, window) -> int:
    """(query, key) pairs a row's mask allows: the causal triangle, or
    its band of ``window`` keys a query, its own among them."""
    if window is None or window >= seq_len:
        return seq_len * (seq_len + 1) // 2
    return window * (window + 1) // 2 + (seq_len - window) * window


def pair_flops(cfg: dict) -> float:
    """One (query, key) pair of one differential layer, forward: two
    maps a pair of heads, each ``2 Dh`` operations for the score and
    ``2 * 2 Dh`` for its share of the 2 Dh-wide values."""
    _, h, _, dh = _sizes(cfg)
    return 2.0 * (h // 2) * (2 * dh + 2 * 2 * dh)


def scan_flops_per_token(cfg: dict) -> float:
    """The recurrence, forward, a token and layer: seven operations a
    state (the decay's product and exponential, the state's two products
    and sum, the read's product and sum)."""
    return 7.0 * cfg["mamba_d_inner"] * cfg["mamba_d_state"]


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Operations the forward and backward passes REQUIRE per token: 6
    per matmul parameter a token meets (the SwiGLUs, the mixers'
    projections, the head: the embedding once, as the head), three times
    the forward count of the allowed pairs a token in each differential
    layer and of the recurrence in each Mamba layer.  Recomputed
    operations (remat) are not counted."""
    d = cfg["hidden_size"]
    plan = layer_plan(cfg)
    matmuls = sum(mixer_matmul_params(cfg, e) for e in plan) \
        + len(plan) * 3 * d * cfg["intermediate_size"] \
        + d * cfg["vocab_size"]
    pairs = sum(attention_pairs(seq_len, e["window"]) / seq_len
                for e in plan if e["kind"] == "diff")
    scans = sum(1 for e in plan if e["kind"] == "mamba")
    return (6.0 * matmuls + 3.0 * pair_flops(cfg) * pairs
            + 3.0 * scan_flops_per_token(cfg) * scans)


def flash_step_cost(cfg: dict, rows: int, seq_len: int, backward: bool,
                    itemsize: int = 2) -> list:
    """[{flops, bytes}] of the step's attention calls, one a map a
    differential layer (two a layer): ``H / 2`` query heads of ``Dh``
    over ``K / 2`` key heads of ``Dh`` and value heads of ``2 Dh``, the
    pairs the layer's mask allows.  Least HBM traffic: the forward reads
    q and writes out and a float32 log-sum-exp a query head and reads k
    and V once a K/V head; the backward reads q, out, dout and the
    log-sum-exp and writes dq a query head, reads k, V and writes dk, dV
    once a K/V head.  The backward's operations are twice the forward's
    (four products, no recomputation counted)."""
    _, h, kv, dh = _sizes(cfg)
    heads, heads_kv = h // 2, kv // 2
    q_bytes, v_bytes = seq_len * dh * itemsize, seq_len * 2 * dh * itemsize
    calls = []
    for entry in layer_plan(cfg):
        if entry["kind"] != "diff":
            continue
        forward = rows * pair_flops(cfg) / 2 \
            * attention_pairs(seq_len, entry["window"])
        if backward:
            one = {"flops": 2 * forward, "bytes": float(rows * (
                heads * (2 * q_bytes + 2 * v_bytes + 4 * seq_len)
                + heads_kv * 2 * (q_bytes + v_bytes)))}
        else:
            one = {"flops": forward, "bytes": float(rows * (
                heads * (q_bytes + v_bytes + 4 * seq_len)
                + heads_kv * (q_bytes + v_bytes)))}
        calls += [one, dict(one)]
    return calls


def scan_call_cost(cfg: dict, rows: int, seq_len: int, backward: bool,
                   itemsize: int = 2) -> dict:
    """One selective scan over ``rows`` rows: the forward reads c, delta,
    B and C and writes y in the activations' type; the backward reads
    those, dy and the float32 state entering each chunk of ``ssm_chunk``
    positions and writes the five cotangents (dA float32).  Operations:
    ``scan_flops_per_token`` forward, twice that backward.  Against the
    table's two peaks this is memory-bound; what binds on the chip is
    the vector unit, for which the table has no rate."""
    e, n = cfg["mamba_d_inner"], cfg["mamba_d_state"]
    wide, narrow = rows * seq_len * e * itemsize, rows * seq_len * n * itemsize
    flops = rows * seq_len * scan_flops_per_token(cfg)
    if not backward:
        return {"flops": flops, "bytes": float(3 * wide + 2 * narrow)}
    states = rows * (seq_len // cfg["ssm_chunk"]) * e * n * 4
    return {"flops": 2 * flops,
            "bytes": float(5 * wide + 4 * narrow + states + e * n * 4)}


def _events(ctx: dict, kernel: str):
    """(calls, device seconds) of the kernel's events in the traced
    window, or None where there are none or the configuration is not
    this one."""
    from benchmarks.harness import trace_reduce
    found = trace_reduce.op_seconds(ctx["trace"], kernel)
    calls = sum(n for n, _ in found.values())
    seconds = sum(s for _, s in found.values())
    if not calls or not seconds or "mamba_d_inner" not in ctx["config"]:
        return None
    return calls, seconds


def scan_roofline_share(ctx: dict, kernel: str, backward: bool):
    """Percent of its roofline that the scan kernel whose trace events
    match ``kernel`` reached: one call's least time times the calls over
    their device time."""
    from benchmarks.harness import peaks
    found = _events(ctx, kernel)
    if found is None:
        return None
    calls, seconds = found
    one = scan_call_cost(ctx["config"], ctx["facts"]["rows"],
                         ctx["facts"]["seq_len"], backward)
    least = peaks.roofline(one["flops"], one["bytes"], ctx["device_kind"])
    return 100.0 * least["min_s"] * calls / seconds


def flash_roofline_share(ctx: dict, kernel: str, backward: bool):
    """Percent of their rooflines that the step's attention calls
    reached together: the least times of the step's calls, summed, times
    the steps of the window over the device time of the kernel's events
    (the calls differ -- a band, two triangles -- so it is not one
    call's cost times the events)."""
    from benchmarks.harness import peaks
    found = _events(ctx, kernel)
    steps = ctx["facts"].get("steps")
    if found is None or not steps:
        return None
    _, seconds = found
    least = sum(peaks.roofline(c["flops"], c["bytes"],
                               ctx["device_kind"])["min_s"]
                for c in flash_step_cost(
                    ctx["config"], ctx["facts"]["rows"],
                    ctx["facts"]["seq_len"], backward))
    devices = max(1, len(ctx["trace"]["device_ops"]))
    return 100.0 * least * steps * devices / seconds
