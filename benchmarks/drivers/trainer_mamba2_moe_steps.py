"""Driver ``trainer_mamba2_moe_steps``: a training step of a decoder whose
layers are each one sublayer -- Mamba-2 mixers, grouped-query attention
without positional encoding, and expert layers of squared-ReLU experts
in a latent beside a full-width shared expert, with a sigmoid router and
its correction bias (one expert-parallel rank's share) -- through
``ray_tpu.train.Trainer(backend="jax", num_workers=1, use_tpu=True)``
and ``make_train_step`` with the next-token loss.

As ``trainer_swa_moe_steps`` (whose norms it reads the tree by): the
window drives the jitted step on the state that set-up built and
stepped; the weights, the batches, the clock, the norms that are
compared and the reference are the benchmark's own.  After the window,
the runtime down and the state freed, the state-space rule alone runs
on the seed's probe at the step's shape (``rule_probe``): a gap of
norms cannot see what the state is kept in (PERF.md section 2).
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
from benchmarks.harness import (compare, mamba2_moe_weights, trace_reduce,
                                traffic as traffic_mod, weights)
from benchmarks.harness.compile_clock import clock as compile_clock

COUNTERS = ("moe_held_choices", "moe_layer_held_max", "moe_load_cv",
            "moe_expert_load_max", "moe_dropped_choices", "moe_balance_loss",
            "moe_bias_abs_max", "ssd_fallback_passes", "ssd_dt_mean")


def _model_kwargs(config: dict, seq_len: int) -> dict:
    """The configuration file's keys -> the program's TransformerConfig
    (``mamba2`` as the keywords of its ``Mamba2Config``)."""
    if config["mamba_hidden_act"] != "silu" \
            or config["mlp_hidden_act"] != "relu2" \
            or not config["use_conv_bias"] or config["mamba_proj_bias"] \
            or config["use_bias"] or config["mlp_bias"] \
            or config["attention_bias"] or config["tie_word_embeddings"] \
            or config["n_group"] != 1 or config["topk_group"] != 1 \
            or config["num_nextn_predict_layers"]:
        raise ValueError("the configuration is not the one this driver "
                         "was written for")
    return dict(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], d_ff=config["moe_intermediate_size"],
        max_seq_len=seq_len, remat=config["remat"],
        norm_eps=config["layer_norm_epsilon"], rope="none",
        layer_pattern=tuple(mamba2_moe_weights.pattern_of(config)),
        mamba2=dict(num_heads=config["mamba_num_heads"],
                    head_dim=config["mamba_head_dim"],
                    n_groups=config["n_groups"],
                    state_size=config["ssm_state_size"],
                    conv_kernel=config["conv_kernel"],
                    chunk=config["chunk_size"],
                    norm_groups=config["n_groups"],
                    dt_min=config["time_step_min"],
                    dt_max=config["time_step_max"],
                    dt_floor=config["time_step_floor"]),
        moe_experts=config["n_routed_experts"],
        moe_top_k=config["num_experts_per_tok"],
        moe_norm_topk=config["norm_topk_prob"],
        moe_d_ff=config["moe_intermediate_size"],
        moe_act=config["mlp_hidden_act"],
        moe_latent=config["moe_latent_size"],
        moe_scoring="sigmoid",
        moe_route_scale=config["routed_scaling_factor"],
        moe_bias_rate=config["router_bias_update_rate"],
        moe_shared_width=config["moe_shared_expert_intermediate_size"],
        moe_experts_held=(config["experts_held_first"],
                          config["n_routed_experts_held"]),
        moe_aux_coeff=config["router_aux_loss_coef"],
        moe_alike_tail=config["dispatch_alike_tail"],
        # the checked steps hand their routing to the reference
        moe_report_choices=True)


def transformer_config(kwargs: dict, dtype):
    """The program's configuration from ``_model_kwargs``' plain data."""
    from ray_tpu.models.mamba2 import Mamba2Config
    from ray_tpu.models.transformer import TransformerConfig
    return TransformerConfig(dtype=dtype, **dict(
        kwargs, mamba2=Mamba2Config(**kwargs["mamba2"])))


def rule_probe(config: dict, seed: int, rows: int, length: int) -> dict:
    """The program's rule alone, as the step calls it (operands in the
    configuration's type, both kernels on a TPU), and its ``jax.vjp``
    under the probe's cotangent, on the seed's probe of ``rows`` x
    ``length`` positions -> the reference's ``PROBE_PARTS`` on the host,
    and ``ssd_fallback_passes`` of the probe's own call."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import ssd
    dtype = jnp.dtype(config["dtype"])
    chunk = min(config["chunk_size"], length)

    @jax.jit
    def run(x, dt, a, b, c, dy):
        y, vjp = jax.vjp(lambda x, dt, b, c: ssd.ssd_rule(
            x, dt, a, b, c, chunk=chunk), x.astype(dtype), dt,
            b.astype(dtype), c.astype(dtype))
        return (y, *vjp(dy.astype(dtype)))

    reference = _reference(config)
    out = run(*reference.rule_probe_inputs(seed, config, rows, length))
    return ({name: np.asarray(x, np.float32)
             for name, x in zip(reference.PROBE_PARTS, out)},
            ssd.fallback_passes())


def _train_fn(c: dict) -> dict:
    """Runs inside the Train worker (a thread of this process)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu import train
    from ray_tpu.models.transformer import make_train_state, make_train_step

    config, seed = c["config"], c["seed"]
    dtype = jnp.dtype(config["dtype"])
    cfg = transformer_config(c["model_kwargs"], dtype)
    b1 = config["optimizer"]["b1"]
    box = []

    def build(key):
        state, tx = make_train_state(
            key, cfg, learning_rate=config["optimizer"]["learning_rate"])
        box.append(tx)
        return state

    state = jax.jit(build)(weights.seed_key(seed))
    start = mamba2_moe_weights.make_decoder(seed, config, dtype)
    want = jax.tree.map(lambda a: (a.shape, a.dtype), state["params"])
    have = jax.tree.map(lambda a: (a.shape, a.dtype), start)
    if want != have:
        raise ValueError(f"the program's parameter tree is not the "
                         f"benchmark's: {want} against {have}")
    n_params = sum(a.size for a in jax.tree.leaves(start))
    state["params"] = start
    del start
    train_step = make_train_step(cfg, box[0])
    first, held = config["experts_held_first"], config["n_routed_experts_held"]
    n_experts = config["n_routed_experts"]

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
    counted = {name: [] for name in COUNTERS}

    def fetch(metrics) -> float:
        got = jax.device_get({k: metrics[k] for k in COUNTERS + ("loss",)})
        for name in COUNTERS:
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
        mamba2_moe_weights.make_decoder(seed, config, dtype)).items()}
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


def _reference(config: dict):
    return importlib.import_module(
        "benchmarks.reference." + config["reference"])


def run(cell: dict, config: dict, traffic: dict, seed: int, seconds: float,
        trace_dir) -> dict:
    # First, so that a program without the Mamba-2 kind fails here, in
    # seconds, before any runtime is started.
    from ray_tpu.models.mamba2 import Mamba2Config  # noqa: F401

    import ray_tpu
    from ray_tpu.models.moe import chunk_rows
    from ray_tpu.train import Trainer

    kwargs = _model_kwargs(config, traffic["seq_len"])
    counted_params = mamba2_moe_weights.parameter_count(config)
    if counted_params != config["parameters"]:
        raise ValueError(f"the tree holds {counted_params} parameters, the "
                         f"configuration file says {config['parameters']}")
    batches = traffic_mod.generate(traffic, seed,
                                   vocab_size=config["vocab_size"])
    steps = cell["check"]["steps"]
    job = dict(config=config, seed=seed, seconds=seconds,
               model_kwargs=kwargs, batches=batches, check_steps=steps,
               trace_dir=trace_dir)
    ray_tpu.init(num_cpus=4, num_tpus=1)
    try:
        trainer = Trainer(backend="jax", num_workers=1, use_tpu=True)
        try:
            (out,) = trainer.run(_train_fn, config=job)
        finally:
            trainer.shutdown()
    finally:
        ray_tpu.shutdown()
    gc.collect()
    probe, probe_fallback = rule_probe(config, seed, traffic["rows"],
                                       traffic["seq_len"])

    tokens_per_step = traffic["rows"] * traffic["seq_len"]
    window_s = out["done"][-1] - out["t_start"]
    rate = out["steps"] * tokens_per_step / window_s
    bad = sum(1 for x in out["losses"] if not math.isfinite(x))
    step_s = np.diff(np.array([out["t_start"]] + out["done"]))
    inner = step_s[1:-1] if len(step_s) > 2 else step_s
    counted = out["counted"]
    in_window = {k: v[steps:] for k, v in counted.items()}
    sizes = (tokens_per_step, config["n_routed_experts"],
             config["n_routed_experts_held"], config["num_experts_per_tok"])
    first_chunk = chunk_rows(*sizes, config["dispatch_alike_tail"])[0]
    layer_held = np.array(in_window["moe_layer_held_max"])
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
            "ssd_dt_mean": float(np.mean(in_window["ssd_dt_mean"])),
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
                   # the step's mixers (a mean over them, every step) and
                   # the probe's own call
                   "ssd_fallback_passes": float(np.sum(
                       counted["ssd_fallback_passes"])) + probe_fallback},
        "first_batches": batches[:steps],
        "first_choices": out["first_choices"],
    }


def follow_reference(cell: dict, config: dict, seed: int, batches,
                     **how) -> dict:
    """The configuration's plain reference over the first steps.
    ``how``: ``choices`` (the program's experts, to be followed and
    checked) and the controls' ``precision``, ``state``, ``decay``,
    ``skip``, ``norm_groups``, ``act``, ``latent``, ``learning_rate``."""
    import jax.numpy as jnp
    return _reference(config).follow(
        lambda: mamba2_moe_weights.make_decoder(
            seed, config, jnp.dtype(config["dtype"])),
        batches, config, steps=cell["check"]["steps"], **how)


def rule_numbers(config: dict, seed: int, program_probe: dict,
                 **how) -> dict:
    """``ssd_rule_gap`` and ``ssd_rule_grad_gap``: the program's probe
    against the reference's recurrence on the same inputs (``how``: the
    controls' ``state``, ``decay``)."""
    reference = _reference(config)
    rows, length = program_probe["y"].shape[:2]
    ref = reference.rule_probe(
        reference.rule_probe_inputs(seed, config, rows, length), **how)
    return reference.rule_gaps(program_probe, ref)


def check(cell: dict, config: dict, seed: int, result: dict) -> dict:
    """-> name -> (value, note) for every number compared: the window's
    own object against the plain reference (which follows the experts the
    program chose and holds each choice to its own ``score + bias``), the
    rule alone against the recurrence on the seed's probe, and the
    window's counts."""
    ref = follow_reference(cell, config, seed, result["first_batches"],
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
