"""Driver ``trainer_swa_moe_steps``: a training step of a decoder whose
attention layers are of two kinds in one model -- grouped-query
attention under a window, and over everything before, with different
head counts and rotary tables, each with a head-wise output gate --
over a leading dense layer and sparse expert layers with a scaled
softmax router and a shared expert (one expert-parallel rank's share),
through ``ray_tpu.train.Trainer(backend="jax", num_workers=1,
use_tpu=True)`` and ``make_train_step`` with the next-token loss.

As ``trainer_gdn_steps``: the window drives the jitted step on the state
that set-up built and stepped (the checked steps are the warm-up); the
weights, the batches, the clock, the norms that are compared and the
reference are the benchmark's own.  The model is a layer pattern of the
leading dense layer's run and one period (``swa_moe_weights.pattern_of``
in the program's words: ``layer_pattern``), so the parameter tree holds
a run's stack and a tuple of the period's stacks.
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
from benchmarks.harness import (compare, swa_moe_weights, trace_reduce,
                                traffic as traffic_mod, weights)
from benchmarks.harness.compile_clock import clock as compile_clock

COUNTERS = ("moe_held_choices", "moe_layer_held_max", "moe_load_cv",
            "moe_expert_load_max", "moe_dropped_choices", "moe_balance_loss",
            "attn_gate_mean", "attn_window_gate_mean")
#: The names of the two rotary tables, by the configuration's
#: ``rope_parameters`` groups.
TABLES = {"full_attention": "global", "sliding_attention": "local"}


def rope_tables(config: dict) -> dict:
    """The configuration's ``rope_parameters`` groups as the keywords of
    the program's ``RopeTable``, by the names the runs use."""
    out = {}
    for group, rope in config["rope_parameters"].items():
        table = {"theta": float(rope["rope_theta"]),
                 "rotary_dim": int(config["head_dim"] * rope.get(
                     "partial_rotary_factor", 1))}
        if rope.get("rope_type", "default") == "yarn":
            table.update(
                factor=float(rope["factor"]),
                original_max_position=rope[
                    "original_max_position_embeddings"],
                beta_fast=float(rope["beta_fast"]),
                beta_slow=float(rope["beta_slow"]),
                attention_factor=float(rope["attention_factor"]))
        elif rope.get("rope_type", "default") != "default":
            raise ValueError(f"rope_type {rope['rope_type']!r}")
        out[TABLES[group]] = table
    return out


def layer_pattern(config: dict) -> tuple:
    """``swa_moe_weights.pattern_of`` in the program's words."""
    def run(like, count):
        says = [f"heads={like['heads']}"]
        if like["window"] is not None:
            says.append(f"window={like['window']}")
        says.append(f"rope={TABLES[like['type']]}")
        return ("mha:" + ",".join(says),
                "dense" if like["ffn"] == "dense" else "moe", count)

    return tuple(
        run(*entry) if isinstance(entry[0], dict)
        else (tuple(run(*r) for r in entry[0]), entry[1])
        for entry in swa_moe_weights.pattern_of(config))


def _model_kwargs(config: dict, seq_len: int) -> dict:
    """The configuration file's keys -> the program's TransformerConfig
    (``rope_tables`` as the keywords of its ``RopeTable``s)."""
    if config["gating"] != "per-head" or config["attention_bias"] \
            or config["moe_router_logit_softcapping"] \
            or config["moe_apply_router_weight_on_input"] \
            or config["tie_word_embeddings"]:
        raise ValueError("the configuration is not the one this driver "
                         "was written for")
    return dict(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], d_ff=config["intermediate_size"],
        max_seq_len=seq_len, remat=config["remat"],
        norm_eps=config["rms_norm_eps"], attn_out_gate="head",
        rope_tables=rope_tables(config),
        layer_pattern=layer_pattern(config),
        moe_experts=config["num_experts"],
        moe_top_k=config["num_experts_per_tok"],
        moe_norm_topk=config["norm_topk_prob"],
        moe_d_ff=config["moe_intermediate_size"],
        moe_route_scale=config["moe_routed_scaling_factor"],
        moe_shared_width=config["shared_expert_intermediate_size"],
        moe_experts_held=(config["experts_held_first"],
                          config["num_experts_held"]),
        moe_aux_coeff=config["router_aux_loss_coef"],
        moe_alike_tail=config["dispatch_alike_tail"],
        # the checked steps hand their routing to the reference
        moe_report_choices=True)


def transformer_config(kwargs: dict, dtype):
    """The program's configuration from ``_model_kwargs``' plain data."""
    from ray_tpu.models.transformer import RopeTable, TransformerConfig
    return TransformerConfig(dtype=dtype, **dict(
        kwargs, rope_tables={name: RopeTable(**table) for name, table
                             in kwargs["rope_tables"].items()}))


def leaf_norms(tree):
    """{leaf label: [layers of its stack] or [1]} of L2 norms, labelled
    as the reference labels them: a leaf of a run's stack
    (``layers.<entry>.``) reduces over everything but its leading axis,
    one of a period's (``layers.<entry>.<run>.``) over everything but
    its two leading axes, (period, layer of the run), flattened."""
    import jax
    import jax.numpy as jnp

    def label(path):
        return ".".join(str(getattr(k, "key", getattr(k, "idx", k)))
                        for k in path)

    out = {}
    for name in ("embed", "ln_f", "lm_head"):
        out[name] = jnp.sqrt(jnp.sum(jnp.square(
            tree[name].astype(jnp.float32))))[None]
    for e, entry in enumerate(tree["layers"]):
        lead = 1 if isinstance(entry, dict) else 2
        for path, leaf in jax.tree_util.tree_flatten_with_path(entry)[0]:
            sq = jnp.square(leaf.astype(jnp.float32))
            out[f"layers.{e}.{label(path)}"] = jnp.sqrt(jnp.sum(
                sq, axis=tuple(range(lead, sq.ndim)))).reshape(-1)
    return out


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
    start = swa_moe_weights.make_decoder(seed, config, dtype)
    want = jax.tree.map(lambda a: (a.shape, a.dtype), state["params"])
    have = jax.tree.map(lambda a: (a.shape, a.dtype), start)
    if want != have:
        raise ValueError(f"the program's parameter tree is not the "
                         f"benchmark's: {want} against {have}")
    n_params = sum(a.size for a in jax.tree.leaves(start))
    state["params"] = start
    del start
    train_step = make_train_step(cfg, box[0])
    first, held = config["experts_held_first"], config["num_experts_held"]
    n_experts = config["num_experts"]

    # From the experts the step reports, on the device: the most
    # token-choices a single layer held (what decides how many dispatch
    # chunks the step ran; the step's own counters are means over the
    # layers), and the coefficient of variation of all experts' loads,
    # the mean over the layers.
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
        swa_moe_weights.make_decoder(seed, config, dtype)).items()}

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
        "bytes_in_use_after": int(left), "parameters": int(n_params),
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
    # First, so that a program whose ``mha`` runs cannot differ fails
    # here, in seconds, before any runtime is started.
    from ray_tpu.models.transformer import RopeTable  # noqa: F401

    import ray_tpu
    from ray_tpu.models.moe import chunk_rows
    from ray_tpu.train import Trainer

    kwargs = _model_kwargs(config, traffic["seq_len"])
    counted_params = swa_moe_weights.parameter_count(config)
    if counted_params != config["parameters"]:
        raise ValueError(f"the tree holds {counted_params} parameters, the "
                         f"configuration file says {config['parameters']}")
    batches = traffic_mod.generate(traffic, seed,
                                   vocab_size=config["vocab_size"])
    steps = cell["check"]["steps"]
    job = dict(config=config, seed=seed, seconds=seconds,
               model_kwargs=kwargs, batches=batches, check_steps=steps,
               trace_dir=trace_dir)
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
    inner = step_s[1:-1] if len(step_s) > 2 else step_s
    counted = out["counted"]
    in_window = {k: v[steps:] for k, v in counted.items()}
    # the first dispatch chunk's rows a layer, as configured and at the
    # program's default: a layer that holds more runs a further chunk
    sizes = (tokens_per_step, config["num_experts"],
             config["num_experts_held"], config["num_experts_per_tok"])
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
            # steps a twentieth over the median: a host that stalled,
            # unless moe_steps_past_first_chunk counts them too
            "slow_steps": int(np.sum(inner > 1.05 * np.median(inner))),
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
            "moe_load_cv": float(np.mean(in_window["moe_load_cv"])),
            "moe_balance_loss": float(np.mean(
                in_window["moe_balance_loss"])),
            "attn_gate_mean": float(np.mean(in_window["attn_gate_mean"])),
            "attn_window_gate_mean": float(np.mean(
                in_window["attn_window_gate_mean"])),
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
    checked) and the controls' ``precision``, ``window``,
    ``window_heads``, ``attn_gate``, ``yarn``, ``rotary``,
    ``route_scale``, ``shared``, ``learning_rate``."""
    import jax.numpy as jnp
    return _reference(config).follow(
        lambda: swa_moe_weights.make_decoder(
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
