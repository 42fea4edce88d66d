"""Driver ``trainer_gdn_steps``: a training step of a hybrid decoder --
Gated DeltaNet layers and a gated-attention layer a period, sparse
experts with a gated shared expert in every layer (one expert-parallel
rank's share) -- through ``ray_tpu.train.Trainer(backend="jax",
num_workers=1, use_tpu=True)`` and ``make_train_step`` with the
next-token loss plus the router's auxiliary.

As ``trainer_blockdiff_steps``: the window drives the jitted step on the
state that set-up built and stepped (the checked steps are the warm-up);
the weights, the batches, the clock, the norms that are compared and the
reference are the benchmark's own.  The model is a layer pattern of one
period, so the parameter tree holds a tuple of the period's two stacks,
each ``[periods, layers of the run, ...]``.

After the window, the runtime shut down and the state freed, the
program's delta rule runs once more alone: ``jax.vjp`` of
``ops.gated_delta.gated_delta_rule`` at the step's own shape (its rows x
positions x value heads, so the step's two kernel programs) on the
seed's probe, whose heads remember as the initialisation's do not
(``rule_probe``); the reference's token-by-token recurrence and its
``jax.vjp`` are what it is held to.
"""

from __future__ import annotations

import gc
import importlib
import itertools
import json
import math
import sys
import time

import numpy as np

from benchmarks.drivers.trainer_steps import _adam_mu
from benchmarks.harness import (compare, gdn_weights, trace_reduce,
                                traffic as traffic_mod, weights)
from benchmarks.harness.compile_clock import clock as compile_clock

COUNTERS = ("moe_held_choices", "moe_layer_held_max", "moe_expert_load_max",
            "moe_dropped_choices", "moe_balance_loss",
            "moe_shared_gate_mean", "attn_gate_mean", "gdn_state_norm",
            "gdn_decay_mean", "gdn_beta_mean")


def _model_kwargs(config: dict, seq_len: int) -> dict:
    """The configuration file's keys -> the program's TransformerConfig
    (``gdn`` as the keywords of ``models.gdn.GDNConfig``)."""
    periods, delta = gdn_weights.period_of(config)
    return dict(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], d_ff=config["moe_intermediate_size"],
        max_seq_len=seq_len, rope_theta=float(config["rope_theta"]),
        remat=config["remat"], qk_norm=True,
        norm_eps=config["rms_norm_eps"], norm_plus_one=True,
        attn_out_gate=True,
        rotary_dim=int(config["head_dim"] * config["partial_rotary_factor"]),
        gdn=dict(num_key_heads=config["linear_num_key_heads"],
                 num_value_heads=config["linear_num_value_heads"],
                 key_head_dim=config["linear_key_head_dim"],
                 value_head_dim=config["linear_value_head_dim"],
                 conv_kernel=config["linear_conv_kernel_dim"],
                 chunk=config["gdn_chunk"]),
        layer_pattern=(((("gdn", "moe", delta), ("mha", "moe", 1)),
                        periods),),
        moe_experts=config["num_experts"],
        moe_top_k=config["num_experts_per_tok"],
        moe_norm_topk=config["norm_topk_prob"],
        moe_shared_width=config["shared_expert_intermediate_size"],
        moe_shared_gate=True,
        moe_experts_held=(config["experts_held_first"],
                          config["num_experts_held"]),
        moe_aux_coeff=config["router_aux_loss_coef"],
        moe_alike_tail=config["dispatch_alike_tail"],
        # the checked steps hand their routing to the reference
        moe_report_choices=True)


def leaf_norms(tree):
    """{leaf label: [layers of its stack] or [1]} of L2 norms, labelled
    as the reference labels them: a leaf of a period's stack
    (``layers.<entry>.<run>.``) reduces over everything but its two
    leading axes, (period, layer of the run), flattened."""
    import jax
    import jax.numpy as jnp
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        label = ".".join(str(getattr(k, "key", getattr(k, "idx", k)))
                         for k in path)
        sq = jnp.square(leaf.astype(jnp.float32))
        if label.startswith("layers."):
            out[label] = jnp.sqrt(jnp.sum(
                sq, axis=tuple(range(2, sq.ndim)))).reshape(-1)
        else:
            out[label] = jnp.sqrt(jnp.sum(sq))[None]
    return out


def rule_probe(config: dict, seed: int, rows: int, length: int,
               **how) -> dict:
    """The program's rule alone, as the step calls it (operands in the
    configuration's type, the state pass as ``how`` says: by default the
    two kernels on a TPU), and its ``jax.vjp`` under the probe's
    cotangent, on the seed's probe of ``rows`` x ``length`` positions ->
    ``PROBE_PARTS`` on the host (``o``, ``dq``, ``dk``, ``dv`` in the
    configuration's type, ``dg``, ``dbeta`` float32)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.gated_delta import gated_delta_rule
    dtype = jnp.dtype(config["dtype"])
    chunk = min(config["gdn_chunk"], length)

    @jax.jit
    def run(q, k, v, g, beta, do):
        o, vjp = jax.vjp(
            lambda *x: gated_delta_rule(*x, chunk=chunk, **how),
            q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta)
        return (o, *vjp(do.astype(dtype)))

    reference = _reference(config)
    out = run(*reference.rule_probe_inputs(seed, config, rows, length))
    return {name: np.asarray(x)
            for name, x in zip(reference.PROBE_PARTS, out)}


def _train_fn(c: dict) -> dict:
    """Runs inside the Train worker (a thread of this process)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu import train
    from ray_tpu.models.gdn import GDNConfig
    from ray_tpu.models.transformer import (TransformerConfig,
                                            make_train_state,
                                            make_train_step)

    config, seed = c["config"], c["seed"]
    dtype = jnp.dtype(config["dtype"])
    kwargs = dict(c["model_kwargs"])
    cfg = TransformerConfig(dtype=dtype, **dict(
        kwargs, gdn=GDNConfig(**kwargs["gdn"])))
    b1 = config["optimizer"]["b1"]

    # One object: the compiled step with its state.  The program builds
    # its own state (one jitted call); the benchmark's weights from the
    # seed take the place of the program's draw.
    box = []

    def build(key):
        state, tx = make_train_state(
            key, cfg, learning_rate=config["optimizer"]["learning_rate"])
        box.append(tx)
        return state

    state = jax.jit(build)(weights.seed_key(seed))
    start = gdn_weights.make_hybrid(seed, config, dtype)
    want = jax.tree.map(lambda a: (a.shape, a.dtype), state["params"])
    have = jax.tree.map(lambda a: (a.shape, a.dtype), start)
    if want != have:
        raise ValueError(f"the program's parameter tree is not the "
                         f"benchmark's: {want} against {have}")
    state["params"] = start
    del start
    train_step = make_train_step(cfg, box[0])
    first, held = config["experts_held_first"], config["num_experts_held"]
    # the most token-choices a single layer held, counted on the device
    # from the experts the step reports: what decides how many dispatch
    # chunks the step ran (its own counters are means over the layers)
    layer_held = jax.jit(lambda chosen: jnp.max(jnp.sum(
        (chosen >= first) & (chosen < first + held), axis=(1, 2, 3))))

    def step(state, batch):
        state, metrics = train_step(state, batch)
        return state, dict(metrics, moe_layer_held_max=layer_held(
            metrics["moe_choices"]))

    pool = [{"tokens": jnp.asarray(b, jnp.int32)} for b in c["batches"]]
    feed = itertools.cycle(pool)
    norms = jax.jit(leaf_norms)
    change_norms = jax.jit(lambda new, old: leaf_norms(jax.tree.map(
        lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
        new, old)))
    counted = {name: [] for name in COUNTERS}

    def fetch(metrics) -> float:
        # the scalars only: the experts chosen stay on the device
        got = jax.device_get({k: metrics[k] for k in COUNTERS + ("loss",)})
        for name in COUNTERS:
            counted[name].append(float(got[name]))
        return float(got["loss"])

    # The first steps, through the window's own call and feed.
    first_losses, first_choices, grad1 = [], [], None
    for i in range(c["check_steps"]):
        state, metrics = step(state, next(feed))
        first_losses.append(fetch(metrics))
        first_choices.append(np.asarray(metrics["moe_choices"]))
        if i == 0:
            grad1 = {k: np.asarray(v, np.float64) / (1.0 - b1) for k, v in
                     norms(_adam_mu(state["opt"])).items()}
    change = {k: np.asarray(v, np.float64) for k, v in change_norms(
        state["params"],
        gdn_weights.make_hybrid(seed, config, dtype)).items()}

    # The window.
    clock = compile_clock()
    before = clock.snapshot()
    if c["trace_dir"]:
        trace_reduce.start(c["trace_dir"])
    seconds = c["seconds"]
    losses, done, n, pending = [], [], 0, None
    t_start = time.perf_counter()
    while True:
        with jax.profiler.TraceAnnotation("train.step"):
            state, metrics = step(state, next(feed))
            n += 1
        if pending is not None:
            with jax.profiler.TraceAnnotation("train.wait"):
                losses.append(fetch(pending))
            done.append(time.perf_counter())
            train.report(step=n - 1, loss=losses[-1],
                         **{k: v[-1] for k, v in counted.items()})
            if done[-1] - t_start >= seconds:
                break
        pending = metrics
    with jax.profiler.TraceAnnotation("train.wait"):
        losses.append(fetch(metrics))
    done.append(time.perf_counter())
    if c["trace_dir"]:
        trace_reduce.stop()
    after = clock.snapshot()
    device = jax.local_devices()[0]
    stats = device.memory_stats() or {}
    # the program's state is freed before the reference takes the chip
    del state, metrics, pending, pool, feed
    gc.collect()
    left = (device.memory_stats() or {}).get("bytes_in_use", 0)
    return {
        "bytes_in_use_after": int(left),
        "first_losses": first_losses, "first_choices": first_choices,
        "grad1_norm": grad1,
        "change_norm": change, "steps": n, "t_start": t_start,
        "done": done, "losses": losses, "counted": counted,
        "lowerings_in_window": after["lowerings"] - before["lowerings"],
        "compile_before_window": before,
        "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0)),
    }


def _reference(config: dict):
    return importlib.import_module(
        "benchmarks.reference." + config["reference"])


def run(cell: dict, config: dict, traffic: dict, seed: int, seconds: float,
        trace_dir) -> dict:
    # First, so that a program without the delta layers fails here, in
    # seconds, before any runtime is started.
    import ray_tpu.models.gdn  # noqa: F401
    from ray_tpu.ops.gated_delta import kernels_by_default

    import ray_tpu
    from ray_tpu.models.moe import chunk_rows
    from ray_tpu.train import Trainer

    batches = traffic_mod.generate(traffic, seed,
                                   vocab_size=config["vocab_size"])
    steps = cell["check"]["steps"]
    job = dict(config=config, seed=seed, seconds=seconds,
               model_kwargs=_model_kwargs(config, traffic["seq_len"]),
               batches=batches, check_steps=steps, trace_dir=trace_dir)
    # num_tpus is passed: init() never initialises a backend to count.
    ray_tpu.init(num_cpus=4, num_tpus=1)
    try:
        trainer = Trainer(backend="jax", num_workers=1, use_tpu=True)
        try:
            (out,) = trainer.run(_train_fn, config=job)
        finally:
            trainer.shutdown()
    finally:
        ray_tpu.shutdown()
    # the program's rule alone at the step's shape, the runtime down and
    # the state freed (the probe and its gradients are 1.6 GB on the host)
    probe = rule_probe(config, seed, traffic["rows"], traffic["seq_len"])

    tokens_per_step = traffic["rows"] * traffic["seq_len"]
    window_s = out["done"][-1] - out["t_start"]
    rate = out["steps"] * tokens_per_step / window_s
    bad = sum(1 for x in out["losses"] if not math.isfinite(x))
    step_s = np.diff(np.array([out["t_start"]] + out["done"]))
    counted = out["counted"]
    in_window = {k: v[steps:] for k, v in counted.items()}
    # the first dispatch chunk's rows a layer, as configured and at the
    # program's default: a layer that holds more runs a further chunk
    sizes = (tokens_per_step, config["num_experts"],
             config["num_experts_held"], config["num_experts_per_tok"])
    first_chunk = chunk_rows(*sizes, config["dispatch_alike_tail"])[0]
    layer_held = np.array(in_window["moe_layer_held_max"])
    return {
        "attempted": out["steps"] * tokens_per_step,
        "failed": bad * tokens_per_step,
        "t_window_start": out["t_start"],
        "window_s": window_s,
        "memory_peak_bytes": out["memory_peak_bytes"],
        "end_to_end": {"train_tokens_per_s": rate},
        "facts": {
            "steps": out["steps"], "tokens_per_step": tokens_per_step,
            "window_s": window_s, "step_seconds": step_s.tolist(),
            "tokens_per_s": rate,
            "step_ms_min": float(step_s[1:-1].min() * 1e3),
            "step_ms_max": float(step_s[1:-1].max() * 1e3),
            # steps a twentieth over the median: a host that stalled,
            # unless moe_steps_past_first_chunk counts them too
            "slow_steps": int(np.sum(
                step_s[1:-1] > 1.05 * np.median(step_s[1:-1]))),
            "rows": traffic["rows"], "seq_len": traffic["seq_len"],
            "last_loss": out["losses"][-1],
            "moe_held_choices": float(np.mean(in_window["moe_held_choices"])),
            "moe_held_choices_max": float(np.max(
                in_window["moe_held_choices"])),
            "moe_held_choices_min": float(np.min(
                in_window["moe_held_choices"])),
            # a single layer's, counted on the device every step
            "moe_layer_held_max": float(layer_held.max()),
            "moe_first_chunk_rows": first_chunk,
            "moe_steps_past_first_chunk": int(np.sum(
                layer_held > first_chunk)),
            "moe_steps_past_default_chunk": int(np.sum(
                layer_held > chunk_rows(*sizes)[0])),
            "moe_expert_load_max": float(np.max(
                in_window["moe_expert_load_max"])),
            "moe_balance_loss": float(np.mean(
                in_window["moe_balance_loss"])),
            "moe_shared_gate_mean": float(np.mean(
                in_window["moe_shared_gate_mean"])),
            "attn_gate_mean": float(np.mean(in_window["attn_gate_mean"])),
            "gdn_state_norm": float(np.mean(in_window["gdn_state_norm"])),
            "gdn_decay_mean": float(np.mean(in_window["gdn_decay_mean"])),
            "gdn_beta_mean": float(np.mean(in_window["gdn_beta_mean"])),
            "bytes_in_use_after": out["bytes_in_use_after"],
            "compile_before_window": out["compile_before_window"],
        },
        "program": {"losses": out["first_losses"],
                    "grad1_norm": out["grad1_norm"],
                    "change_norm": out["change_norm"],
                    "rule_probe": probe},
        "counts": {"compiles_in_window": out["lowerings_in_window"],
                   "nonfinite_losses": bad,
                   "moe_dropped_choices": float(np.sum(np.abs(
                       counted["moe_dropped_choices"]))),
                   # the probe's (and the step's) state passes that ran
                   # as the scan, not as the kernels: 1 off a TPU
                   "gdn_scan_state_passes": 0 if kernels_by_default()
                   else 1},
        "first_batches": batches[:steps],
        "first_choices": out["first_choices"],
    }


def follow_reference(cell: dict, config: dict, seed: int, batches,
                     **how) -> dict:
    """The configuration's plain reference over the first steps.
    ``how``: ``choices`` (the program's experts, to be followed and
    checked) and the controls' ``precision``, ``decay``, ``state``,
    ``dstate``, ``attn_gate``, ``rotary``, ``shared_gate``,
    ``learning_rate``."""
    import jax.numpy as jnp
    return _reference(config).follow(
        lambda: gdn_weights.make_hybrid(
            seed, config, jnp.dtype(config["dtype"])),
        batches, config, steps=cell["check"]["steps"], probe_seed=seed,
        **how)


def check(cell: dict, config: dict, seed: int, result: dict) -> dict:
    """-> name -> (value, note) for every number compared: the window's
    own object against the plain reference, which follows the experts
    the program chose and holds each choice to its own probabilities
    (``routing_gap``), the program's delta rule alone, forward and
    backward at the step's shape, against the recurrence and its
    ``jax.vjp`` on the seed's probe (``gdn_rule_gap`` over the output,
    ``gdn_rule_grad_gap`` over the five gradients), and the window's
    counts."""
    ref = follow_reference(cell, config, seed, result["first_batches"],
                           choices=result["first_choices"])
    sys.stderr.write(json.dumps({
        "not_compared_loss_gaps": compare.loss_gaps(result["program"], ref),
        "losses": result["program"]["losses"],
        "reference_losses": ref["losses"],
        "reference_loss_parts": ref["loss_parts"]}) + "\n")
    numbers = compare.train_numbers(result["program"], ref)
    numbers["routing_gap"] = ref["routing_gap"]
    numbers.update(_reference(config).rule_gaps(
        result["program"]["rule_probe"], ref["rule_probe"]))
    for name, count in result["counts"].items():
        numbers[name] = (count, "count")
    return numbers
