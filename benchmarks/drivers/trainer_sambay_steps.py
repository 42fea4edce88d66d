"""Driver ``trainer_sambay_steps``: a training step of a slice of a
decoder-hybrid-decoder -- Mamba layers, differential attention under a
window and over everything before, a Gated Memory Unit and a
differential cross-attention layer that read what two of the others hand
on -- through ``ray_tpu.train.Trainer(backend="jax", num_workers=1,
use_tpu=True)`` and ``make_train_step`` with the next-token loss over a
head tied to the embedding.

As ``trainer_gdn_steps``: the window drives the jitted step on the state
that set-up built and stepped (the checked steps are the warm-up); the
weights, the batches, the clock, the norms that are compared and the
reference are the benchmark's own.  The model is a layer pattern of one
run a layer (``sambay_weights.layer_plan``), so the parameter tree holds
a tuple of stacks, each ``[1, ...]``.

After the window, the runtime shut down and the state freed, the
program's selective scan runs once more alone: ``jax.vjp`` of
``ops.selective_scan.selective_scan`` at the step's own shape (its rows
x positions x channels, so the step's two kernel programs) on the seed's
probe, whose states remember as the initialisation's barely show
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
from benchmarks.harness import (compare, sambay_weights, trace_reduce,
                                traffic as traffic_mod, weights)
from benchmarks.harness.compile_clock import clock as compile_clock

COUNTERS = ("ssm_scan_fallback_passes", "ssm_delta_mean", "diff_lambda")


def layer_pattern(config: dict) -> tuple:
    """``sambay_weights.layer_plan`` in the program's words: one run of
    count 1 a layer, its options after the kind."""
    def word(entry):
        # (a memory unit reads the memory by its kind: no option says so)
        says = [f"{name}={entry[name]}" for name in ("window", "writes",
                                                     "reads")
                if entry[name] is not None and entry["kind"] != "gmu"]
        return entry["kind"] + (":" + ",".join(says) if says else "")

    return tuple((word(entry), "dense", 1)
                 for entry in sambay_weights.layer_plan(config))


def _model_kwargs(config: dict, seq_len: int) -> dict:
    """The configuration file's keys -> the program's TransformerConfig
    (``mamba`` as the keywords of ``models.mamba.MambaConfig``)."""
    indices = config["layer_indices"]
    if indices != list(range(indices[0], indices[0] + len(indices))) \
            or len(indices) != config["num_hidden_layers"]:
        raise ValueError(f"layer_indices {indices}: a run of "
                         f"{config['num_hidden_layers']} neighbours")
    if not config["tie_word_embeddings"]:
        raise ValueError("the configuration's head is its embedding")
    return dict(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        d_ff=config["intermediate_size"], max_seq_len=seq_len,
        remat=config["remat"], norm="layernorm",
        norm_eps=config["layer_norm_eps"], rope="none",
        tie_embeddings=True, first_layer_index=indices[0],
        mamba=dict(d_inner=config["mamba_d_inner"],
                   d_state=config["mamba_d_state"],
                   d_conv=config["mamba_d_conv"],
                   dt_rank=config["mamba_dt_rank"],
                   chunk=config["ssm_chunk"]),
        layer_pattern=layer_pattern(config))


def leaf_norms(tree):
    """{leaf label: [1]} of L2 norms, labelled as the reference labels
    them: ``layers.<run>.<leaf>`` reduces over everything but the run's
    one layer."""
    import jax
    import jax.numpy as jnp
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        label = ".".join(str(getattr(k, "key", getattr(k, "idx", k)))
                         for k in path)
        sq = jnp.square(leaf.astype(jnp.float32))
        if label.startswith("layers."):
            out[label] = jnp.sqrt(jnp.sum(sq, axis=tuple(range(1, sq.ndim))))
        else:
            out[label] = jnp.sqrt(jnp.sum(sq))[None]
    return out


def rule_probe(config: dict, seed: int, rows: int, length: int,
               **how) -> dict:
    """The program's scan alone, as the step calls it (``c`` in the
    configuration's type, the kernels on a TPU unless ``how`` says
    otherwise), and its ``jax.vjp`` under the probe's cotangent, on the
    seed's probe of ``rows`` x ``length`` positions -> ``PROBE_PARTS`` on
    the host."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.selective_scan import selective_scan
    dtype = jnp.dtype(config["dtype"])
    chunk = min(config["ssm_chunk"], length)

    @jax.jit
    def run(c, delta, a, b, cc, dy):
        # (the reference's recurrence has no skip: D is nought)
        y, vjp = jax.vjp(
            lambda *x: selective_scan(*x, jnp.zeros(a.shape[:1], a.dtype),
                                      chunk=chunk, **how),
            c.astype(dtype), delta, a, b, cc)
        return (y, *vjp(dy.astype(dtype)))

    reference = _reference(config)
    out = run(*reference.rule_probe_inputs(seed, config, rows, length))
    return {name: np.asarray(x)
            for name, x in zip(reference.PROBE_PARTS, out)}


def _train_fn(c: dict) -> dict:
    """Runs inside the Train worker (a thread of this process)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu import train
    from ray_tpu.models.mamba import MambaConfig
    from ray_tpu.models.transformer import (TransformerConfig,
                                            make_train_state,
                                            make_train_step)

    config, seed = c["config"], c["seed"]
    dtype = jnp.dtype(config["dtype"])
    kwargs = dict(c["model_kwargs"])
    cfg = TransformerConfig(dtype=dtype, **dict(
        kwargs, mamba=MambaConfig(**kwargs["mamba"])))
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
    start = sambay_weights.make_sambay(seed, config, dtype)
    want = jax.tree.map(lambda a: (a.shape, a.dtype), state["params"])
    have = jax.tree.map(lambda a: (a.shape, a.dtype), start)
    if want != have:
        raise ValueError(f"the program's parameter tree is not the "
                         f"benchmark's: {want} against {have}")
    state["params"] = start
    del start
    step = make_train_step(cfg, box[0])

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

    # The first steps, through the window's own call and feed.
    first_losses, grad1 = [], None
    for i in range(c["check_steps"]):
        state, metrics = step(state, next(feed))
        first_losses.append(fetch(metrics))
        if i == 0:
            grad1 = {k: np.asarray(v, np.float64) / (1.0 - b1) for k, v in
                     norms(_adam_mu(state["opt"])).items()}
    change = {k: np.asarray(v, np.float64) for k, v in change_norms(
        state["params"],
        sambay_weights.make_sambay(seed, config, dtype)).items()}

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
        "first_losses": first_losses, "grad1_norm": grad1,
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
    # First, so that a program without the kinds fails here, in seconds,
    # before any runtime is started.
    import ray_tpu.models.diff_attention  # noqa: F401
    import ray_tpu.models.mamba  # noqa: F401

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
    # the program's scan alone at the step's shape, the runtime down and
    # the state freed
    probe = rule_probe(config, seed, traffic["rows"], traffic["seq_len"])

    tokens_per_step = traffic["rows"] * traffic["seq_len"]
    window_s = out["done"][-1] - out["t_start"]
    rate = out["steps"] * tokens_per_step / window_s
    bad = sum(1 for x in out["losses"] if not math.isfinite(x))
    step_s = np.diff(np.array([out["t_start"]] + out["done"]))
    counted = out["counted"]
    in_window = {k: v[steps:] for k, v in counted.items()}
    inner = step_s[1:-1] if len(step_s) > 2 else step_s
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
            "step_ms_min": float(inner.min() * 1e3),
            "step_ms_max": float(inner.max() * 1e3),
            "rows": traffic["rows"], "seq_len": traffic["seq_len"],
            "last_loss": out["losses"][-1],
            "ssm_delta_mean": float(np.mean(in_window["ssm_delta_mean"])),
            # the mean over the three differential layers, first and
            # last step of the window: it moves off lambda_init
            "diff_lambda_first": counted["diff_lambda"][0],
            "diff_lambda_last": counted["diff_lambda"][-1],
            "bytes_in_use_after": out["bytes_in_use_after"],
            "compile_before_window": out["compile_before_window"],
        },
        "program": {"losses": out["first_losses"],
                    "grad1_norm": out["grad1_norm"],
                    "change_norm": out["change_norm"],
                    "rule_probe": probe},
        "counts": {"compiles_in_window": out["lowerings_in_window"],
                   "nonfinite_losses": bad,
                   # steps whose Mamba layers ran the jnp scans and not
                   # the kernels (the step's own counter: 1 off a TPU)
                   "ssm_scan_fallback_passes": float(np.max(
                       counted["ssm_scan_fallback_passes"]))},
        "first_batches": batches[:steps],
    }


def follow_reference(cell: dict, config: dict, seed: int, batches,
                     **how) -> dict:
    """The configuration's plain reference over the first steps.
    ``how``: the controls' ``precision``, ``state``, ``window``, ``lam``,
    ``subln``, ``cross_kv``, ``memory_from``, ``kv_cotangent``,
    ``learning_rate``."""
    import jax.numpy as jnp
    return _reference(config).follow(
        lambda: sambay_weights.make_sambay(
            seed, config, jnp.dtype(config["dtype"])),
        batches, config, steps=cell["check"]["steps"], probe_seed=seed,
        **how)


def check(cell: dict, config: dict, seed: int, result: dict) -> dict:
    """-> name -> (value, note) for every number compared: the window's
    own object against the plain reference (``grad1_norm_gap`` over
    every leaf is what sees the cotangents of what two layers hand on),
    the program's scan alone, forward and backward at the step's shape,
    against the recurrence and its ``jax.vjp`` on the seed's probe
    (``ssm_rule_gap`` over the output, ``ssm_rule_grad_gap`` over the
    five gradients), and the window's counts."""
    ref = follow_reference(cell, config, seed, result["first_batches"])
    sys.stderr.write(json.dumps({
        "not_compared_loss_gaps": compare.loss_gaps(result["program"], ref),
        "losses": result["program"]["losses"],
        "reference_losses": ref["losses"]}) + "\n")
    numbers = compare.train_numbers(result["program"], ref)
    numbers.update(_reference(config).rule_gaps(
        result["program"]["rule_probe"], ref["rule_probe"]))
    for name, count in result["counts"].items():
        numbers[name] = (count, "count")
    return numbers
