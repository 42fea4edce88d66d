"""Driver ``trainer_blockdiff_steps``: a block-diffusion training step of
a sparse-expert decoder (one expert-parallel rank's share) through
``ray_tpu.train.Trainer(backend="jax", num_workers=1, use_tpu=True)``
and ``make_train_step`` with the block-diffusion objective.

As ``trainer_steps``: the window drives the jitted step on the state
that set-up built and stepped (the checked steps are the warm-up); the
weights, the batches, the noise, the clock, the norms that are compared
and the reference are the benchmark's own.  A row of ``L`` data tokens
runs as ``2L`` positions; tokens per second count data tokens.
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

from benchmarks.drivers.trainer_steps import _adam_mu, _leaf_norms
from benchmarks.harness import (compare, moe_weights, trace_reduce,
                                traffic as traffic_mod, weights)
from benchmarks.harness.compile_clock import clock as compile_clock

COUNTERS = ("moe_held_choices", "moe_expert_load_max",
            "moe_dropped_choices", "moe_balance_loss", "masked_tokens")


def _model_kwargs(config: dict, seq_len: int) -> dict:
    """The configuration file's keys -> the program's TransformerConfig."""
    return dict(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], d_ff=config["moe_intermediate_size"],
        max_seq_len=2 * seq_len, rope_theta=float(config["rope_theta"]),
        remat=config["remat"], qk_norm=True,
        norm_eps=config["rms_norm_eps"], moe_experts=config["num_experts"],
        moe_top_k=config["num_experts_per_tok"],
        moe_norm_topk=config["norm_topk_prob"],
        moe_experts_held=(config["experts_held_first"],
                          config["num_experts_held"]),
        moe_aux_coeff=config["router_aux_loss_coef"],
        # the checked steps hand their routing to the reference
        moe_report_choices=True)


def make_batches(config: dict, traffic: dict, seed: int) -> list:
    """The pool of batches ``{"tokens", "noisy", "weight"}``: packed
    documents with ids below the mask id (the generator's extra,
    shifted-target position dropped), and the block noise -- one ``t ~
    U(t_min, 1)`` a block, each token masked with probability ``t``,
    weight ``1 / t`` where masked -- from the seed's own stream, so the
    program and the reference get the same noised rows."""
    bd = config["block_diffusion"]
    block, mask_id = bd["block_length"], bd["mask_token_id"]
    clean = traffic_mod.generate(traffic, seed, vocab_size=mask_id)[:, :, :-1]
    rng = traffic_mod.rng_for(seed, "block_noise")
    pool, rows, length = clean.shape
    t = rng.uniform(bd["t_min"], 1.0, (pool, rows, length // block))
    t = np.repeat(t, block, axis=2)
    masked = rng.random((pool, rows, length)) < t
    noisy = np.where(masked, mask_id, clean).astype(np.int32)
    weight = np.where(masked, 1.0 / t, 0.0).astype(np.float32)
    return [{"tokens": clean[i], "noisy": noisy[i], "weight": weight[i]}
            for i in range(pool)]


def _train_fn(c: dict) -> dict:
    """Runs inside the Train worker (a thread of this process)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu import train
    from ray_tpu.models import block_diffusion
    from ray_tpu.models.transformer import (TransformerConfig,
                                            make_train_state,
                                            make_train_step)

    config, seed = c["config"], c["seed"]
    dtype = jnp.dtype(config["dtype"])
    cfg = TransformerConfig(dtype=dtype, **c["model_kwargs"])
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
    start = moe_weights.make_sparse_decoder(seed, config, dtype)
    want = jax.tree.map(lambda a: (a.shape, a.dtype), state["params"])
    have = jax.tree.map(lambda a: (a.shape, a.dtype), start)
    if want != have:
        raise ValueError(f"the program's parameter tree is not the "
                         f"benchmark's: {want} against {have}")
    state["params"] = start
    del start
    step = make_train_step(cfg, box[0], loss_override=functools.partial(
        block_diffusion.loss_fn, cfg=cfg,
        block=config["block_diffusion"]["block_length"]))

    pool = [{k: jnp.asarray(v) for k, v in b.items()} for b in c["batches"]]
    feed = itertools.cycle(pool)
    leaf_norms = jax.jit(_leaf_norms)
    change_norms = jax.jit(lambda new, old: _leaf_norms(jax.tree.map(
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
                     leaf_norms(_adam_mu(state["opt"])).items()}
    change = {k: np.asarray(v, np.float64) for k, v in change_norms(
        state["params"],
        moe_weights.make_sparse_decoder(seed, config, dtype)).items()}

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


def run(cell: dict, config: dict, traffic: dict, seed: int, seconds: float,
        trace_dir) -> dict:
    # First, so that a program without the objective fails here, in
    # seconds, before any runtime is started.
    import ray_tpu.models.block_diffusion  # noqa: F401

    import ray_tpu
    from ray_tpu.train import Trainer

    batches = make_batches(config, traffic, seed)
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
            "rows": traffic["rows"], "seq_len": traffic["seq_len"],
            "positions_per_row": 2 * traffic["seq_len"],
            "last_loss": out["losses"][-1],
            "moe_held_choices": float(np.mean(in_window["moe_held_choices"])),
            "moe_held_choices_max": float(np.max(
                in_window["moe_held_choices"])),
            "moe_held_choices_min": float(np.min(
                in_window["moe_held_choices"])),
            "moe_expert_load_max": float(np.max(
                in_window["moe_expert_load_max"])),
            "moe_balance_loss": float(np.mean(
                in_window["moe_balance_loss"])),
            "masked_tokens": float(np.mean(in_window["masked_tokens"])),
            "bytes_in_use_after": out["bytes_in_use_after"],
            "compile_before_window": out["compile_before_window"],
        },
        "program": {"losses": out["first_losses"],
                    "grad1_norm": out["grad1_norm"],
                    "change_norm": out["change_norm"]},
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
    checked) and the controls' ``precision``, ``mask``,
    ``learning_rate``."""
    import jax.numpy as jnp
    reference = importlib.import_module(
        "benchmarks.reference." + config["reference"])
    return reference.follow(
        lambda: moe_weights.make_sparse_decoder(
            seed, config, jnp.dtype(config["dtype"])),
        batches, config, steps=cell["check"]["steps"], **how)


def check(cell: dict, config: dict, seed: int, result: dict) -> dict:
    """-> name -> (value, note) for every number compared: the window's
    own object against the plain reference, which follows the experts
    the program chose and holds each choice to its own probabilities
    (``routing_gap``), and the window's counts."""
    ref = follow_reference(cell, config, seed, result["first_batches"],
                           choices=result["first_choices"])
    sys.stderr.write(json.dumps({
        "not_compared_loss_gaps": compare.loss_gaps(result["program"], ref),
        "losses": result["program"]["losses"],
        "reference_losses": ref["losses"]}) + "\n")
    numbers = compare.train_numbers(result["program"], ref)
    numbers["routing_gap"] = ref["routing_gap"]
    for name, count in result["counts"].items():
        numbers[name] = (count, "count")
    return numbers
