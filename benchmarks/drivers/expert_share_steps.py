"""What a training cell on one expert-parallel rank's share runs whatever
its mixers are: the window loop (``train_fn``), the run's result
(``run``), the reference it follows and the numbers it compares
(``follow_reference``, ``rule_numbers``, ``check``), through
``ray_tpu.train.Trainer(backend="jax", num_workers=1, use_tpu=True)``
and ``make_train_step`` with the next-token loss.

The window drives the jitted step on the state that set-up built and
stepped; the weights, the batches, the clock, the norms that are
compared and the reference are the benchmark's own.  After the window,
the runtime down and the state freed, the mixer's rule alone runs on the
seed's probe at the step's shape (the driver's ``rule_probe``): a gap of
norms cannot see what the state is kept in.

A driver hands its own module, which holds what is the configuration's:

- ``COUNTERS``: the counters the step reports, fetched every step;
- ``FALLBACK``: the one of them that counts the rule's ``jnp`` passes;
- ``MEANS``: those whose mean over the window is a fact;
- ``EXPERTS``: the configuration's keys of the first held expert, the
  held experts and all experts;
- ``WEIGHTS``: its weight maker (``make_decoder``, ``parameter_count``);
- ``model_kwargs(config, seq_len)``, ``transformer_config(kwargs,
  dtype)`` and ``rule_probe(config, seed, rows, length)``.
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
from benchmarks.drivers.trainer_swa_moe_steps import leaf_norms
from benchmarks.harness import (compare, trace_reduce,
                                traffic as traffic_mod, weights)
from benchmarks.harness.compile_clock import clock as compile_clock


def train_fn(c: dict) -> dict:
    """Runs inside the Train worker (a thread of this process);
    ``c["driver"]`` names the driver's module."""
    import jax
    import jax.numpy as jnp

    from ray_tpu import train
    from ray_tpu.models.transformer import make_train_state, make_train_step

    driver = importlib.import_module(c["driver"])
    counters = driver.COUNTERS
    config, seed = c["config"], c["seed"]
    dtype = jnp.dtype(config["dtype"])
    cfg = driver.transformer_config(c["model_kwargs"], dtype)
    b1 = config["optimizer"]["b1"]
    box = []

    def build(key):
        state, tx = make_train_state(
            key, cfg, learning_rate=config["optimizer"]["learning_rate"])
        box.append(tx)
        return state

    state = jax.jit(build)(weights.seed_key(seed))
    start = driver.WEIGHTS.make_decoder(seed, config, dtype)
    want = jax.tree.map(lambda a: (a.shape, a.dtype), state["params"])
    have = jax.tree.map(lambda a: (a.shape, a.dtype), start)
    if want != have:
        raise ValueError(f"the program's parameter tree is not the "
                         f"benchmark's: {want} against {have}")
    n_params = sum(a.size for a in jax.tree.leaves(start))
    state["params"] = start
    del start
    train_step = make_train_step(cfg, box[0])
    first, held, n_experts = (config[k] for k in driver.EXPERTS)

    # From the experts the step reports, on the device: the most
    # token-choices a single layer held, and the coefficient of
    # variation of all experts' loads, the mean over the layers.
    @jax.jit
    def routed(chosen):
        held_here = jnp.sum((chosen >= first) & (chosen < first + held),
                            axis=(1, 2, 3))
        load = jnp.sum(jax.nn.one_hot(chosen.reshape(chosen.shape[0], -1),
                                      n_experts, dtype=jnp.float32), axis=1)
        return {"moe_layer_held_max": jnp.max(held_here),
                "moe_load_cv": jnp.mean(jnp.std(load, axis=-1)
                                        / jnp.mean(load, axis=-1))}

    def step(state, batch):
        state, metrics = train_step(state, batch)
        return state, dict(metrics, **routed(metrics["moe_choices"]))

    pool = [{"tokens": jnp.asarray(b, jnp.int32)} for b in c["batches"]]
    feed = itertools.cycle(pool)
    norms = jax.jit(leaf_norms)
    change_norms = jax.jit(lambda new, old: leaf_norms(jax.tree.map(
        lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
        new, old)))
    counted = {name: [] for name in counters}

    def fetch(metrics) -> float:
        got = jax.device_get({k: metrics[k] for k in counters + ("loss",)})
        for name in counters:
            counted[name].append(float(got[name]))
        return float(got["loss"])

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
        driver.WEIGHTS.make_decoder(seed, config, dtype)).items()}
    bias = np.asarray(state["moe_bias"])

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
    del state, metrics, pending, pool, feed
    gc.collect()
    left = (device.memory_stats() or {}).get("bytes_in_use", 0)
    return {
        "bytes_in_use_after": int(left), "parameters": int(n_params),
        "first_losses": first_losses, "first_choices": first_choices,
        "grad1_norm": grad1, "change_norm": change, "moe_bias": bias,
        "steps": n, "t_start": t_start, "done": done, "losses": losses,
        "counted": counted,
        "lowerings_in_window": after["lowerings"] - before["lowerings"],
        "compile_before_window": before,
        "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0)),
    }


def reference(config: dict):
    return importlib.import_module(
        "benchmarks.reference." + config["reference"])


def run(driver, cell: dict, config: dict, traffic: dict, seed: int,
        seconds: float, trace_dir) -> dict:
    """The cell's run as ``benchmarks/run.py`` takes it, ``driver`` the
    driver's module."""
    import ray_tpu
    from ray_tpu.models.moe import chunk_rows
    from ray_tpu.train import Trainer

    kwargs = driver.model_kwargs(config, traffic["seq_len"])
    counted_params = driver.WEIGHTS.parameter_count(config)
    if counted_params != config["parameters"]:
        raise ValueError(f"the tree holds {counted_params} parameters, the "
                         f"configuration file says {config['parameters']}")
    batches = traffic_mod.generate(traffic, seed,
                                   vocab_size=config["vocab_size"])
    steps = cell["check"]["steps"]
    job = dict(driver=driver.__name__, config=config, seed=seed,
               seconds=seconds, model_kwargs=kwargs, batches=batches,
               check_steps=steps, trace_dir=trace_dir)
    ray_tpu.init(num_cpus=4, num_tpus=1)
    try:
        trainer = Trainer(backend="jax", num_workers=1, use_tpu=True)
        try:
            (out,) = trainer.run(train_fn, config=job)
        finally:
            trainer.shutdown()
    finally:
        ray_tpu.shutdown()
    gc.collect()
    probe, probe_fallback = driver.rule_probe(config, seed, traffic["rows"],
                                              traffic["seq_len"])

    tokens_per_step = traffic["rows"] * traffic["seq_len"]
    window_s = out["done"][-1] - out["t_start"]
    rate = out["steps"] * tokens_per_step / window_s
    bad = sum(1 for x in out["losses"] if not math.isfinite(x))
    step_s = np.diff(np.array([out["t_start"]] + out["done"]))
    inner = step_s[1:-1] if len(step_s) > 2 else step_s
    counted = out["counted"]
    in_window = {k: v[steps:] for k, v in counted.items()}
    first, held, n_experts = (config[k] for k in driver.EXPERTS)
    first_chunk = chunk_rows(tokens_per_step, n_experts, held,
                             config["num_experts_per_tok"],
                             config["dispatch_alike_tail"])[0]
    layer_held = np.array(in_window["moe_layer_held_max"])
    # the step's mixers (a mean over them, every step) and the probe's
    # own call
    fallback = float(np.sum(counted[driver.FALLBACK])) + probe_fallback
    sys.stderr.write(json.dumps({"parameters": out["parameters"]}) + "\n")
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
            "tokens_per_s": rate, "parameters": out["parameters"],
            "step_ms_min": float(inner.min() * 1e3),
            "step_ms_max": float(inner.max() * 1e3),
            "slow_steps": int(np.sum(inner > 1.05 * np.median(inner))),
            "rows": traffic["rows"], "seq_len": traffic["seq_len"],
            "last_loss": out["losses"][-1],
            "moe_held_choices": float(np.mean(in_window["moe_held_choices"])),
            "moe_layer_held_max": float(layer_held.max()),
            "moe_first_chunk_rows": first_chunk,
            "moe_steps_past_first_chunk": int(np.sum(
                layer_held > first_chunk)),
            "moe_expert_load_max": float(np.max(
                in_window["moe_expert_load_max"])),
            "moe_load_cv": float(np.mean(in_window["moe_load_cv"])),
            "moe_balance_loss": float(np.mean(
                in_window["moe_balance_loss"])),
            "moe_bias_abs_max": float(np.max(in_window["moe_bias_abs_max"])),
            **{name: float(np.mean(in_window[name]))
               for name in driver.MEANS},
            driver.FALLBACK: fallback,
            "bytes_in_use_after": out["bytes_in_use_after"],
            "compile_before_window": out["compile_before_window"],
        },
        "program": {"losses": out["first_losses"],
                    "grad1_norm": out["grad1_norm"],
                    "change_norm": out["change_norm"],
                    "moe_bias": out["moe_bias"], "rule_probe": probe},
        "counts": {"compiles_in_window": out["lowerings_in_window"],
                   "nonfinite_losses": bad,
                   "moe_dropped_choices": float(np.sum(np.abs(
                       counted["moe_dropped_choices"]))),
                   driver.FALLBACK: fallback},
        "first_batches": batches[:steps],
        "first_choices": out["first_choices"],
    }


def follow_reference(driver, cell: dict, config: dict, seed: int, batches,
                     **how) -> dict:
    """The configuration's plain reference over the first steps.
    ``how``: ``choices`` (the program's experts, to be followed and
    checked) and the reference's controls."""
    import jax.numpy as jnp
    return reference(config).follow(
        lambda: driver.WEIGHTS.make_decoder(seed, config,
                                            jnp.dtype(config["dtype"])),
        batches, config, steps=cell["check"]["steps"], **how)


def rule_numbers(config: dict, seed: int, program_probe: dict,
                 **how) -> dict:
    """The rule's two gaps: the program's probe against the reference's
    recurrence on the same inputs (``how``: the rule's controls)."""
    ref_module = reference(config)
    rows, length = program_probe[ref_module.PROBE_PARTS[0]].shape[:2]
    ref = ref_module.rule_probe(
        ref_module.rule_probe_inputs(seed, config, rows, length), **how)
    return ref_module.rule_gaps(program_probe, ref)


def check(driver, cell: dict, config: dict, seed: int,
          result: dict) -> dict:
    """-> name -> (value, note) for every number compared: the window's
    own object against the plain reference (which follows the experts the
    program chose and holds each choice to its own ``score + bias``), the
    rule alone against the recurrence on the seed's probe, and the
    window's counts."""
    ref = follow_reference(driver, cell, config, seed,
                           result["first_batches"],
                           choices=result["first_choices"])
    sys.stderr.write(json.dumps({
        "not_compared_loss_gaps": compare.loss_gaps(result["program"], ref),
        "losses": result["program"]["losses"],
        "reference_losses": ref["losses"],
        "moe_bias_equal": bool(np.array_equal(
            result["program"]["moe_bias"], ref["moe_bias"]))}) + "\n")
    numbers = compare.train_numbers(result["program"], ref)
    numbers["routing_gap"] = ref["routing_gap"]
    numbers.update(rule_numbers(config, seed,
                                result["program"]["rule_probe"]))
    for name, count in result["counts"].items():
        numbers[name] = (count, "count")
    return numbers
