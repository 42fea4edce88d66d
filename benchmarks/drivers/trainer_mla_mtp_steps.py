"""Driver ``trainer_mla_mtp_steps``: a training step of a latent-attention
sparse-expert decoder with a multi-token-prediction module (one
expert-parallel rank's share) through ``ray_tpu.train.Trainer(
backend="jax", num_workers=1, use_tpu=True)`` and ``make_train_step``
with the multi-token objective.

As ``trainer_blockdiff_steps``: the window drives the jitted step on the
state that set-up built and stepped (the checked steps are the warm-up);
the weights, the batches, the clock, the norms that are compared and the
reference are the benchmark's own.  The model is a layer pattern (one
dense-FFN layer, then expert layers), so the parameter tree holds a
tuple of stacks and the module; the routers' correction bias is state
beside the parameters, read after the checked steps and compared with
the reference's exactly.
"""

from __future__ import annotations

import functools
import gc
import importlib
import itertools
import json
import math
import sys
import time

import numpy as np

from benchmarks.drivers.trainer_steps import _adam_mu
from benchmarks.harness import (compare, mla_weights, trace_reduce,
                                traffic as traffic_mod, weights)
from benchmarks.harness.compile_clock import clock as compile_clock

COUNTERS = ("moe_held_choices", "moe_expert_load_max",
            "moe_dropped_choices", "moe_load_cv", "moe_bias_abs_max",
            "main_loss", "mtp_loss")


def _model_kwargs(config: dict, seq_len: int) -> dict:
    """The configuration file's keys -> the program's TransformerConfig
    (``mla`` as the keywords of ``models.mla.MLAConfig``)."""
    dense = config["first_k_dense_replace"]
    return dict(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        d_ff=config["intermediate_size"], max_seq_len=seq_len,
        rope_theta=float(config["rope_theta"]), remat=config["remat"],
        norm_eps=config["rms_norm_eps"],
        mla=dict(q_lora_rank=config["q_lora_rank"],
                 kv_lora_rank=config["kv_lora_rank"],
                 qk_nope_head_dim=config["qk_nope_head_dim"],
                 qk_rope_head_dim=config["qk_rope_head_dim"],
                 v_head_dim=config["v_head_dim"],
                 rope_interleave=config["rope_interleave"]),
        layer_pattern=(("mla", "dense", dense),
                       ("mla", "moe", config["num_hidden_layers"] - dense)),
        mtp_depth=config["num_nextn_predict_layers"],
        moe_experts=config["n_routed_experts"],
        moe_top_k=config["num_experts_per_tok"],
        moe_norm_topk=config["norm_topk_prob"],
        moe_d_ff=config["moe_intermediate_size"],
        moe_scoring=config["scoring_func"],
        moe_route_scale=config["routed_scaling_factor"],
        moe_shared_width=(config["n_shared_experts"]
                          * config["moe_intermediate_size"]),
        moe_bias_rate=config["bias_update_rate"],
        moe_experts_held=(config["experts_held_first"],
                          config["n_routed_experts_held"]),
        moe_aux_coeff=0.0, moe_alike_tail=config["dispatch_alike_tail"],
        # the checked steps hand their routing to the reference
        moe_report_choices=True)


def leaf_norms(tree):
    """{leaf label: [layers of its stack] or [1]} of L2 norms, labelled
    as the reference labels them: a leaf of a stack (``layers.<run>.``,
    ``mtp.layers.``) reduces over everything but the depth axis."""
    import jax
    import jax.numpy as jnp
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        label = ".".join(str(getattr(k, "key", getattr(k, "idx", k)))
                         for k in path)
        sq = jnp.square(leaf.astype(jnp.float32))
        if label.startswith("layers.") or label.startswith("mtp.layers."):
            out[label] = jnp.sqrt(jnp.sum(sq, axis=tuple(range(1, sq.ndim))))
        else:
            out[label] = jnp.sqrt(jnp.sum(sq))[None]
    return out


def _train_fn(c: dict) -> dict:
    """Runs inside the Train worker (a thread of this process)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu import train
    from ray_tpu.models import mtp
    from ray_tpu.models.mla import MLAConfig
    from ray_tpu.models.transformer import (TransformerConfig,
                                            make_train_state,
                                            make_train_step)

    config, seed = c["config"], c["seed"]
    dtype = jnp.dtype(config["dtype"])
    kwargs = dict(c["model_kwargs"])
    cfg = TransformerConfig(dtype=dtype, **dict(
        kwargs, mla=MLAConfig(**kwargs["mla"])))
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
    start = mla_weights.make_latent_moe(seed, config, dtype)
    want = jax.tree.map(lambda a: (a.shape, a.dtype), state["params"])
    have = jax.tree.map(lambda a: (a.shape, a.dtype), start)
    if want != have:
        raise ValueError(f"the program's parameter tree is not the "
                         f"benchmark's: {want} against {have}")
    state["params"] = start
    del start
    step = make_train_step(cfg, box[0], loss_override=functools.partial(
        mtp.loss_fn, cfg=cfg, coeff=config["mtp_loss_coef"]))

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
        mla_weights.make_latent_moe(seed, config, dtype)).items()}
    first_bias = np.asarray(state["moe_bias"])

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
        "first_bias": first_bias, "grad1_norm": grad1,
        "change_norm": change, "steps": n, "t_start": t_start,
        "done": done, "losses": losses, "counted": counted,
        "lowerings_in_window": after["lowerings"] - before["lowerings"],
        "compile_before_window": before,
        "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0)),
    }


def run(cell: dict, config: dict, traffic: dict, seed: int, seconds: float,
        trace_dir) -> dict:
    # First, so that a program without the module fails here, in
    # seconds, before any runtime is started.
    import ray_tpu.models.mla  # noqa: F401
    import ray_tpu.models.mtp  # noqa: F401

    import ray_tpu
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

    tokens_per_step = traffic["rows"] * traffic["seq_len"]
    window_s = out["done"][-1] - out["t_start"]
    rate = out["steps"] * tokens_per_step / window_s
    bad = sum(1 for x in out["losses"] if not math.isfinite(x))
    step_s = np.diff(np.array([out["t_start"]] + out["done"]))
    counted = out["counted"]
    in_window = {k: v[steps:] for k, v in counted.items()}
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
            # steps a twentieth over the median: further dispatch chunks
            # (moe_held_choices_max) or a host that stalled
            "slow_steps": int(np.sum(
                step_s[1:-1] > 1.05 * np.median(step_s[1:-1]))),
            "rows": traffic["rows"], "seq_len": traffic["seq_len"],
            "last_loss": out["losses"][-1],
            "main_loss": in_window["main_loss"][-1],
            "mtp_loss": in_window["mtp_loss"][-1],
            "moe_held_choices": float(np.mean(in_window["moe_held_choices"])),
            "moe_held_choices_max": float(np.max(
                in_window["moe_held_choices"])),
            "moe_held_choices_min": float(np.min(
                in_window["moe_held_choices"])),
            "moe_expert_load_max": float(np.max(
                in_window["moe_expert_load_max"])),
            "moe_load_cv": float(np.mean(in_window["moe_load_cv"])),
            "moe_load_cv_last": in_window["moe_load_cv"][-1],
            "moe_bias_abs_max": in_window["moe_bias_abs_max"][-1],
            "bytes_in_use_after": out["bytes_in_use_after"],
            "compile_before_window": out["compile_before_window"],
        },
        "program": {"losses": out["first_losses"],
                    "grad1_norm": out["grad1_norm"],
                    "change_norm": out["change_norm"],
                    "moe_bias": out["first_bias"]},
        "counts": {"compiles_in_window": out["lowerings_in_window"],
                   "nonfinite_losses": bad,
                   "moe_dropped_choices": float(np.sum(np.abs(
                       counted["moe_dropped_choices"])))},
        "first_batches": batches[:steps],
        "first_choices": out["first_choices"],
    }


def follow_reference(cell: dict, config: dict, seed: int, batches,
                     **how) -> dict:
    """The configuration's plain reference over the first steps.
    ``how``: ``choices`` (the program's experts, to be followed and
    checked) and the controls' ``precision``, ``rotary``, ``scoring``,
    ``mtp_coeff``, ``shared``, ``learning_rate``."""
    import jax.numpy as jnp
    reference = importlib.import_module(
        "benchmarks.reference." + config["reference"])
    return reference.follow(
        lambda: mla_weights.make_latent_moe(
            seed, config, jnp.dtype(config["dtype"])),
        batches, config, steps=cell["check"]["steps"], **how)


def bias_gap(prog_bias, ref_bias):
    """The largest difference between the program's correction bias and
    the reference's after the checked steps: it is signs of whole
    numbers times one float32 rate, so the limit is 0."""
    prog_bias, ref_bias = np.asarray(prog_bias), np.asarray(ref_bias)
    if prog_bias.shape != ref_bias.shape:
        return math.inf, f"{prog_bias.shape} against {ref_bias.shape}"
    gap = np.abs(prog_bias.astype(np.float64) - ref_bias.astype(np.float64))
    layer, expert = np.unravel_index(int(np.argmax(gap)), gap.shape)
    return float(gap.max()), f"expert layer {layer} expert {expert}"


def check(cell: dict, config: dict, seed: int, result: dict) -> dict:
    """-> name -> (value, note) for every number compared: the window's
    own object against the plain reference, which follows the experts
    the program chose and holds each choice to its own ``score + bias``
    (``routing_gap``), the bias after the steps (``moe_bias_gap``), and
    the window's counts."""
    ref = follow_reference(cell, config, seed, result["first_batches"],
                           choices=result["first_choices"])
    sys.stderr.write(json.dumps({
        "not_compared_loss_gaps": compare.loss_gaps(result["program"], ref),
        "losses": result["program"]["losses"],
        "reference_losses": ref["losses"],
        "reference_loss_parts": ref["loss_parts"]}) + "\n")
    numbers = compare.train_numbers(result["program"], ref)
    numbers["routing_gap"] = ref["routing_gap"]
    numbers["moe_bias_gap"] = bias_gap(result["program"]["moe_bias"],
                                       ref["moe_bias"])
    for name, count in result["counts"].items():
        numbers[name] = (count, "count")
    return numbers
