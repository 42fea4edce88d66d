"""Driver ``trainer_steps``: a dense-decoder training step through
``ray_tpu.train.Trainer(backend="jax", num_workers=1, use_tpu=True)``.

The window drives ``make_train_step``'s jitted step on the state that
set-up built and stepped three times (the same object: the three checked
steps are the warm-up).  From the program the benchmark takes only the
system under test: the weights, the batches, the clock, the norms that
are compared and the reference are the benchmark's own.
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

from benchmarks.harness import (compare, trace_reduce,
                                traffic as traffic_mod, weights)
from benchmarks.harness.compile_clock import clock as compile_clock


def _model_kwargs(config: dict, seq_len: int) -> dict:
    """The configuration file's keys -> the program's TransformerConfig."""
    if config["num_key_value_heads"] != config["num_attention_heads"]:
        raise ValueError("the program's transformer is multi-head only")
    return dict(vocab_size=config["vocab_size"], d_model=config["hidden_size"],
                n_layers=config["num_hidden_layers"],
                n_heads=config["num_attention_heads"],
                d_ff=config["intermediate_size"], max_seq_len=seq_len,
                rope_theta=config["rope_theta"], remat=config["remat"])


def _leaf_norms(tree):
    """{leaf label: [n_layers] or [1]} of L2 norms: stacked layer leaves
    reduce over everything but the depth axis."""
    import jax
    import jax.numpy as jnp
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        label = ".".join(str(getattr(k, "key", k)) for k in path)
        sq = jnp.square(leaf.astype(jnp.float32))
        if label.startswith("layers."):
            out[label] = jnp.sqrt(jnp.sum(sq, axis=tuple(range(1, sq.ndim))))
        else:
            out[label] = jnp.sqrt(jnp.sum(sq))[None]
    return out


def _adam_mu(opt_state):
    for entry in opt_state:
        if hasattr(entry, "mu") and hasattr(entry, "nu"):
            return entry.mu
    raise ValueError("no Adam moments in the optimizer state")


def _train_fn(c: dict) -> dict:
    """Runs inside the Train worker (a thread of this process)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu import train
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
    start = weights.make_dense_decoder(seed, config, dtype)
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
    leaf_norms = jax.jit(_leaf_norms)
    change_norms = jax.jit(lambda new, old: _leaf_norms(jax.tree.map(
        lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
        new, old)))

    # The first steps, through the window's own call and feed.
    first_losses, grad1 = [], None
    for i in range(c["check_steps"]):
        state, metrics = step(state, next(feed))
        first_losses.append(float(metrics["loss"]))
        if i == 0:
            grad1 = {k: np.asarray(v, np.float64) / (1.0 - b1) for k, v in
                     leaf_norms(_adam_mu(state["opt"])).items()}
    change = {k: np.asarray(v, np.float64) for k, v in change_norms(
        state["params"],
        weights.make_dense_decoder(seed, config, dtype)).items()}

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
                losses.append(float(pending["loss"]))
            done.append(time.perf_counter())
            train.report(step=n - 1, loss=losses[-1])
            if done[-1] - t_start >= seconds:
                break
        pending = metrics
    with jax.profiler.TraceAnnotation("train.wait"):
        losses.append(float(metrics["loss"]))
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
        "done": done, "losses": losses,
        "lowerings_in_window": after["lowerings"] - before["lowerings"],
        "compile_before_window": before,
        "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0)),
    }


def run(cell: dict, config: dict, traffic: dict, seed: int, seconds: float,
        trace_dir) -> dict:
    import ray_tpu
    from ray_tpu.train import Trainer

    batches = traffic_mod.generate(traffic, seed,
                                   vocab_size=config["vocab_size"])
    job = dict(config=config, seed=seed, seconds=seconds,
               model_kwargs=_model_kwargs(config, traffic["seq_len"]),
               batches=batches, check_steps=cell["check"]["steps"],
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
            "rows": traffic["rows"], "seq_len": traffic["seq_len"],
            "last_loss": out["losses"][-1],
            "bytes_in_use_after": out["bytes_in_use_after"],
            "compile_before_window": out["compile_before_window"],
        },
        "program": {"losses": out["first_losses"],
                    "grad1_norm": out["grad1_norm"],
                    "change_norm": out["change_norm"]},
        "counts": {"compiles_in_window": out["lowerings_in_window"],
                   "nonfinite_losses": bad},
        "first_batches": batches[:cell["check"]["steps"]],
    }


def follow_reference(cell: dict, config: dict, seed: int, batches,
                     **how) -> dict:
    """The configuration's plain reference over the first steps.
    ``how`` (``precision``, ``batch_rows``) is for the controls."""
    import jax.numpy as jnp
    reference = importlib.import_module(
        "benchmarks.reference." + config["reference"])
    return reference.follow(
        lambda: weights.make_dense_decoder(seed, config,
                                           jnp.dtype(config["dtype"])),
        batches, config, steps=cell["check"]["steps"], **how)


def check(cell: dict, config: dict, seed: int, result: dict) -> dict:
    """-> name -> (value, note) for every number compared: the window's
    own object against the plain reference, and the window's counts."""
    ref = follow_reference(cell, config, seed, result["first_batches"])
    sys.stderr.write(json.dumps({
        "not_compared_loss_gaps": compare.loss_gaps(result["program"], ref),
        "losses": result["program"]["losses"],
        "reference_losses": ref["losses"]}) + "\n")
    numbers = compare.train_numbers(result["program"], ref)
    for name, count in result["counts"].items():
        numbers[name] = (count, "count")
    return numbers
