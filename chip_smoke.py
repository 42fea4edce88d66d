"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the two accelerator paths end to end, in ONE process (the chip
belongs to one process; every child the runtime starts is pinned to the
CPU), through the entry points a user calls:

  1. live runtime   ``ray_tpu.init`` (default ``scheduler_backend="jax"``,
                    thread-mode workers), an in-process ``Cluster`` plus
                    one ``node_host`` child, a few thousand
                    ``@ray_tpu.remote`` tasks of several resource shapes;
  2. scheduler      the BASELINE config-5 problem (1M tasks x 256 classes x
     kernel         10k nodes x 8 resources): ``BatchSolver.solve_matrices``
                    picks the fused Pallas fill and its answer is checked on
                    the host; then single live ticks through the program a
                    raylet runs, the fused fill compared EQUAL to the jnp scan
                    on the same device and to the entry point's answer;
  3. trainer        ``ray_tpu.train.Trainer(backend="jax", use_tpu=True)``
                    taking steps on a 200M-parameter model (``TPU_MODEL``),
                    the flash kernels in the compiled step; 3b the mixed
                    latent-attention stack, 3c the hybrid delta-rule one,
                    each kernel first compared with its jnp form.

With more than one chip visible it also runs the sharded solve and the
dp/sp/tp + ep + pp programs on the real devices; with one it says those
legs did not run (never that they passed).

Every leg is a function of its sizes and of the platform it must find:
``main()`` runs them at full width on ``tpu``; tests/test_chip_smoke.py
runs the same functions at tiny sizes with ``platform="cpu"`` (Pallas
kernels in interpret mode).  There is no fallback inside ``main()``:
without a TPU it exits non-zero before anything runs, and any failed
check is the process's failure.

The last line of stdout is one JSON object:
  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
Wall-clock figures printed before it are set-up facts, not benchmark
results.
"""

import functools
import json
import os
import re
import sys
import time

import numpy as np


def check(cond, what: str) -> None:
    """A failed check is the process's failure (``assert`` would vanish
    under ``python -O``)."""
    if not cond:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


def _version(package: str) -> str:
    from importlib import metadata
    try:
        return metadata.version(package)
    except metadata.PackageNotFoundError:
        return "n/a"


def device_facts() -> dict:
    import jax
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


# ---------------------------------------------------------------------------
# Leg 1 — live runtime.
# ---------------------------------------------------------------------------

def leg_live_runtime(platform: str = "tpu", num_nodes: int = 4,
                     num_tasks: int = 3000, remote_tasks: int = 200) -> dict:
    """init -> in-process Cluster (+ one node_host child) -> tasks of
    several resource shapes -> all results fetched; then every raylet's
    device solver must have run with zero fallbacks and zero device
    errors, its world state resident on ``platform``."""
    import ray_tpu
    from ray_tpu._private.cluster import Cluster
    from ray_tpu._private.config import get_config
    from ray_tpu._private.debug import watchdog

    cluster = Cluster(initialize_head=True, head_node_args=dict(
        num_cpus=4, resources={"head_only": 64.0}))
    ray_tpu.init(_cluster=cluster)
    try:
        check(get_config().scheduler_backend == "jax"
              and get_config().worker_process_mode == "thread",
              "defaults are scheduler_backend=jax, thread-mode workers")
        for _ in range(num_nodes - 1):
            cluster.add_node(num_cpus=4, resources={"spoke": 64.0})
        check(cluster.wait_for_nodes(num_nodes), "in-process nodes joined")
        raylets = cluster.raylets()

        @ray_tpu.remote
        def f(i):
            return i + 1

        # Several scheduling classes; "spoke" work cannot run on the
        # head, so the head's solve must spill it.
        shapes = [dict(num_cpus=1), dict(num_cpus=2), dict(num_cpus=0.5),
                  dict(num_cpus=1, resources={"spoke": 1.0}),
                  dict(num_cpus=0.5, resources={"spoke": 2.0}),
                  dict(num_cpus=1, resources={"head_only": 1.0})]
        refs = [f.options(**shapes[i % len(shapes)]).remote(i)
                for i in range(num_tasks)]
        check(ray_tpu.get(refs, timeout=300)
              == [i + 1 for i in range(num_tasks)], "task results")

        # One process per chip: with this process holding the device, a
        # node_host child must come up on the CPU and run a pinned burst.
        handle = cluster.add_remote_node(num_cpus=2,
                                         resources={"child": 1000.0})

        @ray_tpu.remote(resources={"child": 1.0})
        def where(i):
            import jax
            return (os.getpid(), os.environ.get("JAX_PLATFORMS"),
                    jax.default_backend(), i)

        got = ray_tpu.get([where.remote(i) for i in range(remote_tasks)],
                          timeout=300)
        check([g[3] for g in got] == list(range(remote_tasks)),
              "node_host burst results")
        check({g[:3] for g in got} == {(handle.proc.pid, "cpu", "cpu")},
              f"node_host child ran on the CPU in its own process: "
              f"{sorted({g[:3] for g in got})}")

        solvers = []
        for raylet in raylets:
            ctm = raylet.cluster_task_manager
            check(ctm.tick_stats["jnp_fallbacks"] == 0,
                  f"raylet {raylet.node_id.hex()[:8]} jnp_fallbacks == 0: "
                  f"{ctm.tick_stats['jnp_fallbacks']}")
            solver = ctm._jax_solver
            if solver is None:
                continue            # never saw a queue deeper than one
            stats = solver.stats
            check(stats["ticks"] > 0 and stats["fallbacks"] == 0
                  and stats["device_errors"] == 0,
                  f"solver ran clean: {stats}")
            for key in ("avail_t", "total_t"):
                devs = {d.platform for d in solver._state[key].devices()}
                check(devs == {platform},
                      f"resident {key} on {platform}: {devs}")
            solvers.append({"path": solver.last_path, **stats})
        check(cluster.head_node.cluster_task_manager._jax_solver is not None
              and len(solvers) >= 2,
              f"device session engaged on the head and on spill targets "
              f"({len(solvers)} solvers)")
        check(not watchdog.wedge_reports(),
              f"no wedge reports: {watchdog.wedge_reports()}")
        return {"nodes": num_nodes, "tasks": num_tasks,
                "node_host_tasks": remote_tasks, "solvers": solvers}
    finally:
        ray_tpu.shutdown()


# ---------------------------------------------------------------------------
# Leg 2 — the scheduler kernel at full width.
# ---------------------------------------------------------------------------

def build_problem(rng, num_tasks=1_000_000, C=256, N=10_000, R=8):
    """Config 5 of BASELINE.json, Google-cluster-trace shaped.  (The
    benchmark's ``raylet_rounds`` driver keeps its own copy of this
    draw: the yardstick never imports this tool.)"""
    # Heterogeneous fleet: small CPU nodes, big CPU nodes, TPU hosts.
    total = np.zeros((N, R), dtype=np.float32)
    kinds = rng.choice(3, size=N, p=[0.6, 0.3, 0.1])
    total[:, 0] = np.where(kinds == 0, 4, np.where(kinds == 1, 64, 8))  # CPU
    total[:, 1] = np.where(kinds == 0, 16, np.where(kinds == 1, 256, 64))  # mem GB
    total[:, 2] = np.where(kinds == 2, 4, 0)   # TPU chips
    total[:, 3] = rng.integers(0, 2, N)        # GPU-ish custom accel
    for r in range(4, R):
        total[:, r] = rng.integers(0, 8, N)    # custom resources
    used = rng.uniform(0.0, 0.6, size=(N, R)).astype(np.float32)
    avail = np.floor(total * (1.0 - used))

    # Trace-shaped demand: most classes small CPU tasks, a tail of
    # memory-heavy and accelerator classes; counts follow a power law.
    demand = np.zeros((C, R), dtype=np.float32)
    demand[:, 0] = rng.choice([0.5, 1, 2, 4], size=C, p=[0.4, 0.4, 0.15, 0.05])
    demand[:, 1] = rng.choice([1, 2, 4, 16], size=C, p=[0.5, 0.3, 0.15, 0.05])
    accel_classes = rng.random(C) < 0.08
    demand[accel_classes, 2] = rng.choice([1, 4], size=accel_classes.sum())
    raw = rng.pareto(1.5, size=C) + 1.0
    counts = np.floor(raw / raw.sum() * num_tasks).astype(np.int64)
    counts[-1] += num_tasks - counts.sum()
    accel_node = total[:, 2] > 0
    return avail, total, demand, counts, accel_node, accel_classes


def leg_scheduler_kernel(platform: str = "tpu", num_tasks: int = 1_000_000,
                         classes: int = 256, nodes: int = 10_000,
                         resources: int = 8, live_ticks: int = 3) -> dict:
    """The single-device solve (leg 4 is the sharded one): the entry
    point ``BatchSolver.solve_matrices`` with the fill it picks for this
    platform, its answer checked on the host; then single live ticks
    through ``_jit_solve_tick``, the fused fill compared EQUAL to the
    jnp scan on the same inputs and device, the ``ok`` bit, and the
    packed tick decoded EQUAL to the entry point's answer.  Off the chip
    the fused kernel runs in interpret mode."""
    import jax

    from ray_tpu._private.config import get_config
    from ray_tpu.scheduler import jax_backend as jb

    rng = np.random.default_rng(42)
    avail, total, demand, counts, accel_node, accel_class = build_problem(
        rng, num_tasks=num_tasks, C=classes, N=nodes, R=resources)
    # Tick 0 is the whole backlog; later ticks the same volume with the
    # per-class mix rotated onto other demand shapes.
    queues = [np.roll(counts, k) for k in range(max(live_ticks, 1))]

    cfg = get_config()
    prev = cfg.solver_shard_backend
    cfg.solver_shard_backend = "off"
    try:
        solver = jb.BatchSolver()
        dense = [solver.solve_matrices(avail, total, demand, queue,
                                       accel_node, accel_class,
                                       spread_threshold=0.5)
                 for queue in queues]
    finally:
        cfg.solver_shard_backend = prev
    entry_path = solver.last_path
    check(entry_path == ("single/pallas" if platform == "tpu"
                         else "single/jnp"),
          f"solve_matrices took {entry_path}")
    for k, (alloc, queue) in enumerate(zip(dense, queues)):
        usage = alloc.T.astype(np.float64) @ demand.astype(np.float64)
        check((usage <= avail.astype(np.float64) + 1e-2).all(),
              f"solve_matrices tick {k}: capacity")
        check((alloc.sum(axis=1) <= queue).all(),
              f"solve_matrices tick {k}: counts")

    # Single live ticks: what a raylet runs (resident [R, N] world, one
    # tick per program, nnz_max from the solver's own buckets), both
    # fills explicitly on the same device-resident inputs.
    c_pad, n_pad, r_pad = jb.BatchSolver._pads(classes, nodes, resources)
    nnz_seen = max(int((alloc > 0).sum()) for alloc in dense)
    nnz_max = next(b for b in jb.DeviceRuntimeSolver._NNZ_BUCKETS
                   if b >= nnz_seen)
    avail_t, total_t, demand_d, accel_node_d, accel_class_d, cost_d = (
        jax.device_put(x) for x in (
            jb._pad_to(avail.astype(np.float32), (n_pad, r_pad)).T.copy(),
            jb._pad_to(total.astype(np.float32), (n_pad, r_pad)).T.copy(),
            jb._pad_to(demand.astype(np.float32), (c_pad, r_pad)),
            jb._pad_to(accel_node.astype(bool), (n_pad,)),
            jb._pad_to(accel_class.astype(bool), (c_pad,)),
            np.zeros((c_pad, n_pad), np.float32)))
    check({d.platform for d in avail_t.devices()} == {platform},
          f"world state on {platform}")
    for k in range(live_ticks):
        tick_args = (avail_t, total_t, demand_d,
                     jb._pad_to(queues[k].astype(np.float32), (c_pad,)),
                     accel_node_d, accel_class_d, np.float32(0.5), cost_d)
        fused = np.asarray(jb._jit_solve_tick(
            c_pad, n_pad, r_pad, nnz_max, True)(*tick_args))
        scan = np.asarray(jb._jit_solve_tick(
            c_pad, n_pad, r_pad, nnz_max, False)(*tick_args))
        check(np.array_equal(fused, scan),
              f"live tick {k}: fused Pallas fill == jnp scan")
        idx, vals, _, ok, _ = jb._unpack_tick(fused, nnz_max)
        check(ok, f"live tick {k}: ok bit")
        check(np.array_equal(
            jb._dense_alloc(idx, vals, c_pad, n_pad)[:classes, :nodes],
            dense[k]), f"live tick {k}: packed tick == solve_matrices")
    return {"shape": [num_tasks, classes, nodes, resources],
            "padded": [c_pad, n_pad, r_pad], "entry_path": entry_path,
            "placed_tick0": int(dense[0].sum()),
            "nnz_max_seen": nnz_seen, "live_tick_nnz_bucket": nnz_max,
            "live_ticks": live_ticks, "fused_equals_scan": True,
            "tick_equals_entry_point": True}


# ---------------------------------------------------------------------------
# Leg 3 — Trainer steps on a 200M-parameter model.
# ---------------------------------------------------------------------------

#: kwargs of TransformerConfig minus the dtype, and the batch: the
#: 199.8M-parameter model this leg has trained since the chip arrived.
TPU_MODEL = dict(vocab_size=32_000, d_model=1024, n_layers=8, n_heads=16,
                 d_ff=4096, max_seq_len=1024, remat=True)
TPU_BATCH, TPU_SEQ = 8, 1024


def _max_err(a, b) -> float:
    import jax.numpy as jnp
    return float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                 - b.astype(jnp.float32))))


def _train_func(config: dict) -> dict:
    """Runs inside the Train worker (a thread of this process)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu import train
    from ray_tpu.models.transformer import (TransformerConfig, is_period,
                                            make_train_state,
                                            make_train_step)

    model = dict(config["model"])
    if "mla" in model:
        from ray_tpu.models.mla import MLAConfig
        model["mla"] = MLAConfig(**model["mla"])
    if "gdn" in model:
        from ray_tpu.models.gdn import GDNConfig
        model["gdn"] = GDNConfig(**model["gdn"])
    if "mamba" in model:
        from ray_tpu.models.mamba import MambaConfig
        model["mamba"] = MambaConfig(**model["mamba"])
    cfg = TransformerConfig(dtype=jnp.dtype(config["dtype"]), **model)
    state, tx = make_train_state(jax.random.PRNGKey(0), cfg)
    objective = None
    if cfg.mtp_depth:
        from ray_tpu.models import mtp
        objective = functools.partial(mtp.loss_fn, cfg=cfg, coeff=0.3)
    step = make_train_step(cfg, tx, loss_override=objective)
    tokens = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (config["batch"], config["seq"] + 1))
    batch = {"tokens": jnp.asarray(tokens, jnp.int32)}
    compiled = step.lower(state, batch).compile()
    losses = []
    for i in range(config["steps"]):
        state, metrics = compiled(state, batch)
        losses.append(float(metrics["loss"]))
        train.report(step=i, loss=losses[-1])
    leaf = jax.tree.leaves(state["params"])[0]
    text = compiled.as_text()

    def mosaic_calls(*names):
        return [len(re.findall(
            rf'%{name}[.\d]* = [^\n]*"tpu_custom_call"', text))
            for name in names]

    return {"losses": losses,
            # the last step's scalars (the expert layers' counters, the
            # two losses and the bias of a latent-attention model)
            "counters": {k: float(v) for k, v in metrics.items()
                         if v.ndim == 0},
            "mosaic_in_step": "tpu_custom_call" in text,
            "mosaic_bwd_in_step": "flash_attention_bwd" in text,
            # Mosaic calls named for the forward kernel: one, in the
            # forward scan's body, where remat keeps its out and lse;
            # the backward scan's body would hold a second.
            "flash_fwd_calls_in_step": len(re.findall(
                r'%flash_attention_fwd[.\d]* = [^\n]*"tpu_custom_call"',
                text)),
            # the delta rule's fused kernels: the forward once, in the
            # forward scan's body (remat keeps its o and the state
            # entering each grid step), the backward once
            "gated_delta_calls_in_step": mosaic_calls(
                "gated_delta_fwd", "gated_delta_bwd"),
            # the delta layers' convolution: as the rule's kernels
            "causal_conv_calls_in_step": mosaic_calls(
                "causal_conv_fwd", "causal_conv_bwd"),
            # the selective scan's: the forward in both scans' bodies
            # (nothing of it is kept), the backward once, a Mamba layer
            "selective_scan_calls_in_step": mosaic_calls(
                "selective_scan_fwd", "selective_scan_bwd"),
            "flash_bwd_calls_in_step": len(re.findall(
                r'%flash_attention_bwd[.\d]* = [^\n]*"tpu_custom_call"',
                text)),
            # the expert layers' grouped products: three forward and
            # eight backward (remat's second forward of them is dead
            # code) in each of a scanned run's two bodies, the first
            # chunk's and the loop's over further chunks; as many scans
            # hold an expert layer
            "ragged_dot_calls_in_step": mosaic_calls("ragged-dot-none")[0],
            "expert_scans": cfg.mtp_depth + sum(
                ffn == "moe" for entry in cfg.layer_pattern
                for _, ffn, _ in (entry[0] if is_period(entry) else [entry])),
            "param_platforms": sorted({d.platform for d in leaf.devices()}),
            "n_params": sum(int(np.prod(x.shape))
                            for x in jax.tree.leaves(state["params"]))}


def leg_trainer(platform: str = "tpu", model: dict = None, batch: int = None,
                seq: int = None, steps: int = 5, dtype: str = "bfloat16",
                flash_tol: float = 4e-2) -> dict:
    """Trainer(backend="jax", num_workers=1, use_tpu=True) on a head that
    advertises the chip; a repeated batch, so the loss must fall.  On the
    chip the compiled step must contain the Mosaic flash kernels, forward
    and backward, the forward once (remat keeps what it wrote and does
    not run it again in the backward scan), and both must agree with
    ``full_attention`` and its ``jax.grad``, under the causal mask and
    under the block-diffusion mask with grouped K/V heads (off the chip
    the kernels are checked in interpret mode and ``attention()`` takes
    the reference)."""
    import jax
    import jax.numpy as jnp

    import ray_tpu
    from ray_tpu.ops.attention_mask import BlockDiffusion
    from ray_tpu.ops.flash_attention import flash_attention
    from ray_tpu.ops.ring_attention import full_attention
    from ray_tpu.train import Trainer

    model = dict(model or TPU_MODEL)
    batch = batch or TPU_BATCH
    seq = seq or TPU_SEQ
    on_chip = platform == "tpu"

    # Kernel vs reference at the model's attention shape.
    heads, head_dim = model["n_heads"], model["d_model"] // model["n_heads"]
    q, k, v, dout = (jax.random.normal(key, (batch, seq, heads, head_dim),
                                       jnp.float32).astype(jnp.dtype(dtype))
                     for key in jax.random.split(jax.random.PRNGKey(7), 4))

    flash = flash_attention(q, k, v, interpret=not on_chip)
    ref = full_attention(q, k, v)
    flash_err = _max_err(flash, ref)
    check(flash_err <= flash_tol,
          f"flash forward vs full_attention: max abs err {flash_err} "
          f"> {flash_tol} ({dtype})")

    # The Pallas backward against jax.grad of the reference, each
    # gradient's error over the reference gradient's largest entry.
    def grads(fn):
        return jax.grad(lambda q, k, v: jnp.sum(
            fn(q, k, v).astype(jnp.float32) * dout.astype(jnp.float32)),
            (0, 1, 2))(q, k, v)

    def grads_err(got, want):
        return max(
            _max_err(g, w) / float(jnp.max(jnp.abs(w.astype(jnp.float32))))
            for g, w in zip(got, want))

    flash_bwd_err = grads_err(
        grads(functools.partial(flash_attention, interpret=not on_chip)),
        grads(full_attention))
    check(flash_bwd_err <= flash_tol,
          f"flash backward vs grad of full_attention: max err over the "
          f"gradient's max {flash_bwd_err} > {flash_tol} ({dtype})")

    # The same two questions under the block-diffusion mask, each row
    # run as [noised ; clean] (2 x seq positions, blocks of 4), query
    # heads grouped four to a K/V head where the head count allows.
    mask = BlockDiffusion(seq, 4)
    kv_heads = heads // 4 if heads % 4 == 0 else heads
    q, k, v, dout = (
        jax.random.normal(key, (batch, 2 * seq, h, head_dim),
                          jnp.float32).astype(jnp.dtype(dtype))
        for key, h in zip(jax.random.split(jax.random.PRNGKey(8), 4),
                          (heads, kv_heads, kv_heads, heads)))
    bd_flash = functools.partial(flash_attention, mask=mask,
                                 interpret=not on_chip)
    bd_full = functools.partial(full_attention, mask=mask)
    bd_err = _max_err(bd_flash(q, k, v), bd_full(q, k, v))
    check(bd_err <= flash_tol,
          f"block-diffusion grouped flash forward vs full_attention: max "
          f"abs err {bd_err} > {flash_tol} ({dtype})")
    bd_bwd_err = grads_err(grads(bd_flash), grads(bd_full))
    check(bd_bwd_err <= flash_tol,
          f"block-diffusion grouped flash backward vs grad of "
          f"full_attention: {bd_bwd_err} > {flash_tol} ({dtype})")

    # num_tpus is passed: init() never initialises a backend to count.
    ray_tpu.init(num_cpus=4, num_tpus=len(jax.devices()))
    try:
        trainer = Trainer(backend="jax", num_workers=1, use_tpu=True)
        try:
            (result,) = trainer.run(_train_func, config=dict(
                model=model, batch=batch, seq=seq, steps=steps,
                dtype=dtype))
        finally:
            trainer.shutdown()
    finally:
        ray_tpu.shutdown()
    losses = result["losses"]
    check(len(losses) == steps and np.isfinite(losses).all(),
          f"finite losses: {losses}")
    check(losses[-1] < losses[0], f"loss falls on a repeated batch: {losses}")
    check(result["param_platforms"] == [platform],
          f"params on {platform}: {result['param_platforms']}")
    check(result["mosaic_in_step"] == on_chip,
          f"Mosaic flash kernel in the compiled step: "
          f"{result['mosaic_in_step']} (expected {on_chip})")
    check(result["mosaic_bwd_in_step"] == on_chip,
          f"Mosaic flash backward kernel in the compiled step: "
          f"{result['mosaic_bwd_in_step']} (expected {on_chip})")
    check(result["flash_fwd_calls_in_step"] == int(on_chip),
          f"the flash forward kernel once a layer (remat keeps out and "
          f"lse): {result['flash_fwd_calls_in_step']} calls in the "
          f"compiled step (expected {int(on_chip)})")
    return {"model": model, "batch": batch, "seq": seq, "dtype": dtype,
            "params_m": round(result["n_params"] / 1e6, 1),
            "losses": [round(x, 4) for x in losses],
            "flash_in_step": result["mosaic_in_step"],
            "flash_bwd_in_step": result["mosaic_bwd_in_step"],
            "flash_fwd_calls_in_step": result["flash_fwd_calls_in_step"],
            "flash_vs_full_max_abs_err": flash_err,
            "flash_bwd_vs_grad_of_full_max_rel_err": flash_bwd_err,
            "block_diffusion_gqa_flash_vs_full_max_abs_err": bd_err,
            "block_diffusion_gqa_flash_bwd_max_rel_err": bd_bwd_err,
            "block_diffusion_kv_heads": kv_heads,
            "flash_tol": flash_tol}


def _ragged_dot_calls_a_layer(result: dict, on_chip: bool) -> int:
    """The grouped products an expert layer's chunk makes in the compiled
    step, forward and backward: 11 (``models/moe.py``: the backward takes
    the gates' gradient from ``dy @ w2^T``, not from the output made
    again); 0 off the chip, where the product is no custom call."""
    calls, scans = result["ragged_dot_calls_in_step"], result["expert_scans"]
    check(calls == 2 * 11 * scans * int(on_chip),
          f"11 ragged-dot calls a body in the {scans} scans that hold an "
          f"expert layer, two bodies each: {calls} in the compiled step")
    return calls // (2 * scans)


#: The mixed stack at a size the chip takes in seconds: latent
#: attention at the published head sizes (128 + 64 score columns, 128
#: value columns), one dense-FFN layer, two expert layers (8 of 32
#: experts held, 4 a token, a shared expert, the sigmoid router's
#: correction bias) and the multi-token-prediction module: 181.9M
#: parameters.
LATENT_MODEL = dict(
    vocab_size=32_000, d_model=1024, n_heads=16, d_ff=4096, max_seq_len=1024,
    remat=True, norm_eps=1e-6, rope_theta=32e6,
    mla=dict(q_lora_rank=768, kv_lora_rank=256, qk_nope_head_dim=128,
             qk_rope_head_dim=64, v_head_dim=128),
    layer_pattern=(("mla", "dense", 1), ("mla", "moe", 2)), mtp_depth=1,
    moe_experts=32, moe_top_k=4, moe_experts_held=(0, 8), moe_d_ff=512,
    moe_scoring="sigmoid", moe_route_scale=2.5, moe_shared_width=512,
    moe_bias_rate=1e-3, moe_aux_coeff=0.0)


def leg_latent_trainer(platform: str = "tpu", model: dict = None,
                       batch: int = 4, seq: int = 1024, steps: int = 2,
                       dtype: str = "bfloat16",
                       flash_tol: float = 4e-2) -> dict:
    """The mixed layer stack through the same Trainer path, ``steps``
    steps: both flash kernels with the shared rotary key first compared
    with ``full_attention`` and its ``jax.grad`` (all five gradients) at
    the model's attention shape; then the compiled step must hold the
    forward kernel once a run of the layer pattern and once in the
    module, nothing may be dropped, and the routers' correction bias
    must have moved by its rate a step."""
    import jax
    import jax.numpy as jnp

    import ray_tpu
    from ray_tpu.ops.flash_attention import flash_attention
    from ray_tpu.ops.ring_attention import full_attention
    from ray_tpu.train import Trainer

    model = dict(model or LATENT_MODEL)
    on_chip = platform == "tpu"
    heads, m = model["n_heads"], model["mla"]
    shapes = [(batch, seq, heads, m["qk_nope_head_dim"]),
              (batch, seq, heads, m["qk_nope_head_dim"]),
              (batch, seq, heads, m["v_head_dim"]),
              (batch, seq, heads, m["qk_rope_head_dim"]),
              (batch, seq, m["qk_rope_head_dim"]),
              (batch, seq, heads, m["v_head_dim"])]
    *operands, dout = (
        jax.random.normal(key, shape, jnp.float32).astype(jnp.dtype(dtype))
        for key, shape in zip(jax.random.split(jax.random.PRNGKey(9), 6),
                              shapes))

    def kernel(q, k, v, q_rope, k_rope):
        return flash_attention(q, k, v, interpret=not on_chip,
                               q_rope=q_rope, k_rope=k_rope)

    def reference(q, k, v, q_rope, k_rope):
        return full_attention(q, k, v, q_rope=q_rope, k_rope=k_rope)

    def grads(fn):
        return jax.grad(lambda *xs: jnp.sum(
            fn(*xs).astype(jnp.float32) * dout.astype(jnp.float32)),
            (0, 1, 2, 3, 4))(*operands)

    fwd_err = _max_err(kernel(*operands), reference(*operands))
    check(fwd_err <= flash_tol,
          f"latent flash forward vs full_attention: max abs err {fwd_err} "
          f"> {flash_tol} ({dtype})")
    bwd_err = max(
        _max_err(g, w) / float(jnp.max(jnp.abs(w.astype(jnp.float32))))
        for g, w in zip(grads(kernel), grads(reference)))
    check(bwd_err <= flash_tol,
          f"latent flash backward (dq, dk, dv, dq_rope, dk_rope) vs grad of "
          f"full_attention: {bwd_err} > {flash_tol} ({dtype})")

    ray_tpu.init(num_cpus=4, num_tpus=len(jax.devices()))
    try:
        trainer = Trainer(backend="jax", num_workers=1, use_tpu=True)
        try:
            (result,) = trainer.run(_train_func, config=dict(
                model=model, batch=batch, seq=seq, steps=steps,
                dtype=dtype))
        finally:
            trainer.shutdown()
    finally:
        ray_tpu.shutdown()
    losses, counters = result["losses"], result["counters"]
    check(len(losses) == steps and np.isfinite(losses).all(),
          f"finite losses: {losses}")
    check(losses[-1] < losses[0], f"loss falls on a repeated batch: {losses}")
    check(result["param_platforms"] == [platform],
          f"params on {platform}: {result['param_platforms']}")
    # a forward kernel a scanned run and one in the module
    runs = len(model["layer_pattern"]) + model["mtp_depth"]
    check(result["flash_fwd_calls_in_step"] == runs * int(on_chip),
          f"the flash forward kernel once a layer in each of the {runs} "
          f"scans: {result['flash_fwd_calls_in_step']} calls in the "
          f"compiled step (expected {runs * int(on_chip)})")
    check(result["mosaic_bwd_in_step"] == on_chip,
          f"Mosaic flash backward kernel in the compiled step: "
          f"{result['mosaic_bwd_in_step']} (expected {on_chip})")
    check(counters["moe_dropped_choices"] == 0.0,
          f"no choice dropped: {counters}")
    ragged_dot_calls = _ragged_dot_calls_a_layer(result, on_chip)
    check(abs(counters["moe_bias_abs_max"]
              - steps * model["moe_bias_rate"]) < 1e-6,
          f"the correction bias moves by its rate a step: {counters}")
    return {"batch": batch, "seq": seq, "dtype": dtype,
            "params_m": round(result["n_params"] / 1e6, 1),
            "losses": [round(x, 4) for x in losses],
            "counters": {k: round(v, 5) for k, v in counters.items()},
            "flash_fwd_calls_in_step": result["flash_fwd_calls_in_step"],
            "flash_bwd_in_step": result["mosaic_bwd_in_step"],
            "ragged_dot_calls_a_layer": ragged_dot_calls,
            "latent_flash_vs_full_max_abs_err": fwd_err,
            "latent_flash_bwd_max_rel_err": bwd_err,
            "flash_tol": flash_tol}


#: The hybrid stack at a size the chip takes in seconds: one period of
#: two Gated DeltaNet layers at the published head sizes (8 key heads
#: serving 16 value heads of 128, 4 taps, chunks of 64) and one gated
#: attention layer at 256-wide heads (8 query heads on 1 K/V head,
#: rotary on 64 columns, ``1 + w`` norms), every layer with 8 of 32
#: experts held, 4 a token, and a gated shared expert: 131.8M parameters.
HYBRID_MODEL = dict(
    vocab_size=32_000, d_model=1024, n_heads=8, n_kv_heads=1, head_dim=256,
    d_ff=512, max_seq_len=1024, remat=True, norm_eps=1e-6, rope_theta=1e7,
    qk_norm=True, norm_plus_one=True, attn_out_gate=True, rotary_dim=64,
    gdn=dict(num_key_heads=8, num_value_heads=16, key_head_dim=128,
             value_head_dim=128, conv_kernel=4, chunk=64),
    layer_pattern=(((("gdn", "moe", 2), ("mha", "moe", 1)), 1),),
    moe_experts=32, moe_top_k=4, moe_experts_held=(0, 8),
    moe_shared_width=512, moe_shared_gate=True, moe_aux_coeff=0.001)


def leg_hybrid_trainer(platform: str = "tpu", model: dict = None,
                       batch: int = 4, seq: int = 1024, steps: int = 2,
                       dtype: str = "bfloat16",
                       rule_tol: float = 4e-2) -> dict:
    """The hybrid stack through the same Trainer path, ``steps`` steps:
    the delta rule's two fused kernels (the whole rule in VMEM, the
    gradient by hand) first compared with the chunked ``jnp`` form and its
    ``jax.grad`` (all five gradients) at the model's shape, q and k at
    the value heads and at the key heads, and the convolution's two
    kernels with ``silu(causal_conv(.))`` and its ``jax.grad`` at the
    fused projection's shape (off the chip only where the tiny shape is
    one the kernels take); then the compiled step must hold both delta
    kernels, both convolution kernels and both flash kernels, nothing may
    be dropped, and the new counters must read what an untrained model's
    gates read."""
    import jax
    import jax.numpy as jnp

    import ray_tpu
    from ray_tpu.ops import causal_conv as conv_op
    from ray_tpu.ops.gated_delta import gated_delta_rule
    from ray_tpu.train import Trainer

    model = dict(model or HYBRID_MODEL)
    on_chip = platform == "tpu"
    g_ = model["gdn"]
    heads, dk, dv = (g_["num_value_heads"], g_["key_head_dim"],
                     g_["value_head_dim"])
    keys = jax.random.split(jax.random.PRNGKey(11), 6)
    low = jnp.dtype(dtype)

    def unit(x):
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    q = (unit(jax.random.normal(keys[0], (batch, seq, heads, dk)))
         * dk ** -0.5).astype(low)
    k = unit(jax.random.normal(keys[1], (batch, seq, heads, dk))).astype(low)
    v = jax.random.normal(keys[2], (batch, seq, heads, dv)).astype(low)
    # heads that forget within a position and heads that never do
    rate = jnp.exp(jnp.linspace(jnp.log(1e-3), jnp.log(16.0), heads))
    g = -rate * jax.nn.softplus(jax.random.normal(keys[3],
                                                  (batch, seq, heads)))
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], (batch, seq, heads)))
    dout = jax.random.normal(keys[5], (batch, seq, heads, dv))
    chunk = min(g_["chunk"], seq)

    def kernels(*xs):
        return gated_delta_rule(*xs, chunk=chunk, use_pallas=True,
                                interpret=not on_chip)

    def scan(*xs):
        return gated_delta_rule(*xs, chunk=chunk, use_pallas=False)

    def grads(fn):
        return jax.grad(lambda *xs: jnp.sum(
            fn(*xs).astype(jnp.float32) * dout), (0, 1, 2, 3, 4))(*operands)

    def rel_err(got, want):
        return _max_err(got, want) / float(
            jnp.max(jnp.abs(want.astype(jnp.float32))))

    fwd_err = bwd_err = 0.0
    # q and k once a value head, then once a key head, as the layer
    # hands them over
    for every in (1, heads // g_["num_key_heads"]):
        operands = (q[:, :, ::every], k[:, :, ::every], v, g, beta)
        fwd_err = max(fwd_err, rel_err(kernels(*operands), scan(*operands)))
        bwd_err = max([bwd_err] + [rel_err(a, b) for a, b in zip(
            grads(kernels), grads(scan))])
    check(fwd_err <= rule_tol,
          f"gated delta kernels vs the chunked form: max rel err {fwd_err} "
          f"> {rule_tol} ({dtype})")
    check(bwd_err <= rule_tol,
          f"gated delta backward (dq, dk, dv, dg, dbeta) vs grad of the "
          f"chunked scan: {bwd_err} > {rule_tol} ({dtype})")

    # the convolution over q | k | v of every key head of the fused
    # projection [q | k | v | z], as the layer calls it
    hk, ratio = g_["num_key_heads"], heads // g_["num_key_heads"]
    width = 2 * dk + ratio * dv
    qkvz = jax.random.normal(
        keys[0], (batch, seq, hk, width + ratio * dv)).astype(low)
    taps = (0.5 * jax.random.normal(
        keys[1], (hk, width, g_["conv_kernel"]))).astype(low)
    dmixed = jax.random.normal(keys[2], (batch, seq, hk, width))

    def conv_kernels(x, t):
        return conv_op.in_kernels(x, t, interpret=not on_chip)

    def conv_plain(x, t):
        return jax.nn.silu(conv_op.causal_conv(x[..., :width], t))

    conv_err = None
    if conv_op.fits(qkvz.shape, taps.shape):
        got, got_vjp = jax.vjp(conv_kernels, qkvz, taps)
        want, want_vjp = jax.vjp(conv_plain, qkvz, taps)
        conv_err = max([rel_err(got, want)] + [rel_err(a, b) for a, b in zip(
            got_vjp(dmixed), want_vjp(dmixed))])
        check(conv_err <= rule_tol,
              f"convolution kernels (mixed, dqkvz, dtaps) vs silu(causal_conv)"
              f" and its grad: {conv_err} > {rule_tol} ({dtype})")

    ray_tpu.init(num_cpus=4, num_tpus=len(jax.devices()))
    try:
        trainer = Trainer(backend="jax", num_workers=1, use_tpu=True)
        try:
            (result,) = trainer.run(_train_func, config=dict(
                model=model, batch=batch, seq=seq, steps=steps,
                dtype=dtype))
        finally:
            trainer.shutdown()
    finally:
        ray_tpu.shutdown()
    losses, counters = result["losses"], result["counters"]
    check(len(losses) == steps and np.isfinite(losses).all(),
          f"finite losses: {losses}")
    check(losses[-1] < losses[0], f"loss falls on a repeated batch: {losses}")
    check(result["param_platforms"] == [platform],
          f"params on {platform}: {result['param_platforms']}")
    check(result["gated_delta_calls_in_step"] == [int(on_chip)] * 2,
          f"the delta rule's forward kernel in the forward scan's body alone "
          f"and its backward in the backward's: "
          f"{result['gated_delta_calls_in_step']} Mosaic calls")
    check(result["causal_conv_calls_in_step"]
          == [2 * int(on_chip), int(on_chip)]
          and counters["gdn_conv_fallback_passes"] == float(not on_chip),
          f"the convolution's forward kernel in both scans' bodies and its "
          f"backward in one, no pass by the jnp form: "
          f"{result['causal_conv_calls_in_step']} Mosaic calls, "
          f"gdn_conv_fallback_passes "
          f"{counters['gdn_conv_fallback_passes']}")
    check(result["flash_fwd_calls_in_step"] == int(on_chip)
          and result["mosaic_bwd_in_step"] == on_chip,
          f"both flash kernels once in the compiled step: "
          f"{result['flash_fwd_calls_in_step']}, "
          f"{result['mosaic_bwd_in_step']}")
    check(counters["moe_dropped_choices"] == 0.0,
          f"no choice dropped: {counters}")
    ragged_dot_calls = _ragged_dot_calls_a_layer(result, on_chip)
    for name in ("attn_gate_mean", "moe_shared_gate_mean", "gdn_beta_mean"):
        check(0.3 < counters[name] < 0.7, f"{name} near a half: {counters}")
    check(0.0 < counters["gdn_decay_mean"] < 1.0
          and counters["gdn_state_norm"] > 0.0,
          f"the delta layers' state decays and is written: {counters}")
    return {"batch": batch, "seq": seq, "dtype": dtype,
            "params_m": round(result["n_params"] / 1e6, 1),
            "losses": [round(x, 4) for x in losses],
            "counters": {k: round(v, 5) for k, v in counters.items()},
            "gated_delta_calls_in_step": result["gated_delta_calls_in_step"],
            "causal_conv_calls_in_step": result["causal_conv_calls_in_step"],
            "flash_fwd_calls_in_step": result["flash_fwd_calls_in_step"],
            "ragged_dot_calls_a_layer": ragged_dot_calls,
            "causal_conv_max_rel_err": conv_err,
            "gated_delta_vs_scan_max_rel_err": fwd_err,
            "gated_delta_bwd_max_rel_err": bwd_err, "rule_tol": rule_tol}


#: The decoder-hybrid-decoder's four kinds of layer at published widths
#: (hidden 2,560, 40 query heads of 64 on 20 K/V heads, Mamba with 5,120
#: channels of 16 states, LayerNorms with a bias, no rotary, the head
#: tied): a Mamba layer that hands on its scan output, differential
#: attention under a window of 512, a full layer that hands on its keys
#: and values, a Gated Memory Unit and a cross layer; a 1,024-wide
#: SwiGLU in each, 8,192 vocabulary rows: 180.3M parameters.
SAMBAY_MODEL = dict(
    vocab_size=8192, d_model=2560, n_heads=40, n_kv_heads=20, d_ff=1024,
    max_seq_len=2048, remat=True, norm="layernorm", rope="none",
    tie_embeddings=True, first_layer_index=15,
    mamba=dict(d_inner=5120, d_state=16, d_conv=4, dt_rank=160, chunk=64),
    layer_pattern=(("diff:window=512", "dense", 1),
                   ("mamba:writes=memory", "dense", 1),
                   ("diff:writes=kv", "dense", 1), ("gmu", "dense", 1),
                   ("diff:reads=kv", "dense", 1)))


def leg_sambay_trainer(platform: str = "tpu", model: dict = None,
                       batch: int = 1, seq: int = 2048, steps: int = 2,
                       dtype: str = "bfloat16", scan_tol: float = 4e-2,
                       flash_tol: float = 4e-2) -> dict:
    """The state-space and differential-attention kinds through the same
    Trainer path, ``steps`` steps: the selective scan's two kernels
    first compared with the chunked ``jnp`` path and its ``jax.grad``
    (all six gradients) at the model's shape, and both flash kernels
    under the window (20 query heads of 64 over 10 key heads of 64 and
    value heads of 128) with ``full_attention`` and its ``jax.grad``;
    then the compiled step must hold the scan's kernels (the forward in
    both passes, the backward once) and six flash calls each way (two
    maps a differential layer), and the counters must read what an
    untrained model's read."""
    import jax
    import jax.numpy as jnp

    import ray_tpu
    from ray_tpu.ops.attention_mask import SlidingWindow
    from ray_tpu.ops.flash_attention import flash_attention
    from ray_tpu.ops.ring_attention import full_attention
    from ray_tpu.ops.selective_scan import selective_scan
    from ray_tpu.train import Trainer

    model = dict(model or SAMBAY_MODEL)
    on_chip = platform == "tpu"
    m = model["mamba"]
    e, n = m["d_inner"], m["d_state"]
    low = jnp.dtype(dtype)
    keys = jax.random.split(jax.random.PRNGKey(13), 10)

    def rel_err(got, want):
        return _max_err(got, want) / float(
            jnp.max(jnp.abs(want.astype(jnp.float32))))

    # channels that forget within a position and channels that never do
    rate = jnp.exp(jnp.linspace(jnp.log(1e-4), 0.0, e))
    operands = (
        jax.random.normal(keys[0], (batch, seq, e)).astype(low),
        rate * jax.nn.softplus(jax.random.normal(keys[1], (batch, seq, e))),
        -jnp.broadcast_to(jnp.arange(1.0, n + 1), (e, n)),
        jax.random.normal(keys[2], (batch, seq, n)),
        jax.random.normal(keys[3], (batch, seq, n)), jnp.ones((e,)))
    dy = jax.random.normal(keys[4], (batch, seq, e))
    chunk = min(m["chunk"], seq)

    def scan_grads(**how):
        def loss(*xs):
            y = selective_scan(*xs, chunk=chunk, **how)
            return jnp.sum(y.astype(jnp.float32) * dy), y
        grads, y = jax.grad(loss, (0, 1, 2, 3, 4, 5), has_aux=True)(
            *operands)
        return (y, *grads)

    got = scan_grads(use_pallas=True, interpret=not on_chip)
    want = scan_grads(use_pallas=False)
    scan_fwd_err = rel_err(got[0], want[0])
    scan_bwd_err = max(rel_err(a, b) for a, b in zip(got[1:], want[1:]))
    check(scan_fwd_err <= scan_tol,
          f"selective scan kernels vs the jnp scans: max rel err "
          f"{scan_fwd_err} > {scan_tol} ({dtype})")
    check(scan_bwd_err <= scan_tol,
          f"selective scan backward (dc, ddelta, dA, dB, dC, dD) vs grad of "
          f"the jnp scans: {scan_bwd_err} > {scan_tol} ({dtype})")

    h, kv, dh = model["n_heads"] // 2, model["n_kv_heads"] // 2, \
        model["d_model"] // model["n_heads"]
    window = SlidingWindow(min(512, seq // 4))
    q = jax.random.normal(keys[5], (batch, seq, h, dh)).astype(low)
    k = jax.random.normal(keys[6], (batch, seq, kv, dh)).astype(low)
    v = jax.random.normal(keys[7], (batch, seq, kv, 2 * dh)).astype(low)
    dout = jax.random.normal(keys[8], (batch, seq, h, 2 * dh))

    def flash_grads(fn):
        def loss(q, k, v):
            o = fn(q, k, v)
            return jnp.sum(o.astype(jnp.float32) * dout), o
        grads, o = jax.grad(loss, (0, 1, 2), has_aux=True)(q, k, v)
        return (o, *grads)

    got = flash_grads(lambda q, k, v: flash_attention(
        q, k, v, mask=window, interpret=not on_chip))
    want = flash_grads(lambda q, k, v: full_attention(q, k, v, mask=window))
    flash_err = max(rel_err(a, b) for a, b in zip(got, want))
    check(flash_err <= flash_tol,
          f"flash kernels under the window at 64 | 128 columns vs "
          f"full_attention and its grad: {flash_err} > {flash_tol}")

    ray_tpu.init(num_cpus=4, num_tpus=len(jax.devices()))
    try:
        trainer = Trainer(backend="jax", num_workers=1, use_tpu=True)
        try:
            (result,) = trainer.run(_train_func, config=dict(
                model=model, batch=batch, seq=seq, steps=steps,
                dtype=dtype))
        finally:
            trainer.shutdown()
    finally:
        ray_tpu.shutdown()
    losses, counters = result["losses"], result["counters"]
    check(len(losses) == steps and np.isfinite(losses).all(),
          f"finite losses: {losses}")
    check(losses[-1] < losses[0], f"loss falls on a repeated batch: {losses}")
    check(result["param_platforms"] == [platform],
          f"params on {platform}: {result['param_platforms']}")
    check(result["selective_scan_calls_in_step"]
          == [2 * int(on_chip), int(on_chip)],
          f"the scan's forward kernel in both scans' bodies and its "
          f"backward in one: {result['selective_scan_calls_in_step']}")
    check(result["flash_fwd_calls_in_step"] == 6 * int(on_chip)
          and result["flash_bwd_calls_in_step"] == 6 * int(on_chip),
          f"two flash calls a differential layer each way: "
          f"{result['flash_fwd_calls_in_step']}, "
          f"{result['flash_bwd_calls_in_step']}")
    check(counters["ssm_scan_fallback_passes"] == float(not on_chip),
          f"the scan ran as the kernels on the chip: {counters}")
    check(0.5 < counters["diff_lambda"] < 1.0
          and 0.0 < counters["ssm_delta_mean"] < 0.2,
          f"lambda near lambda_init, step sizes in their range: {counters}")
    return {"batch": batch, "seq": seq, "dtype": dtype,
            "params_m": round(result["n_params"] / 1e6, 1),
            "losses": [round(x, 4) for x in losses],
            "counters": {k: round(v, 5) for k, v in counters.items()},
            "selective_scan_calls_in_step":
                result["selective_scan_calls_in_step"],
            "flash_fwd_calls_in_step": result["flash_fwd_calls_in_step"],
            "selective_scan_vs_jnp_max_rel_err": scan_fwd_err,
            "selective_scan_bwd_max_rel_err": scan_bwd_err,
            "window_flash_max_rel_err": flash_err,
            "scan_tol": scan_tol, "flash_tol": flash_tol}


# ---------------------------------------------------------------------------
# Legs 4 and 5 — more than one device.
# ---------------------------------------------------------------------------

def leg_sharded_solve(platform: str = "tpu", nodes: int = 10_240,
                      classes: int = 64, num_tasks: int = 100_000,
                      tick_specs: int = 4096) -> dict:
    """The node-sharded solve on every visible device against the
    single-device kernel: ``BatchSolver.solve_matrices`` and one
    ``DeviceRuntimeSolver`` tick.  ``nodes`` is a multiple of 128 x
    devices so both pad to the same ring and must agree bit for bit."""
    import jax

    from ray_tpu._private.config import get_config
    from ray_tpu.scheduler import jax_backend as jb
    from ray_tpu.scheduler.policy import SchedulingOptions
    from ray_tpu.scheduler.resources import (ClusterResourceView,
                                             NodeResources, ResourceRequest)

    n_dev = len(jax.devices())
    cfg = get_config()
    check(n_dev > 1 and nodes % (jb._GROUP * n_dev) == 0
          and nodes >= cfg.solver_shard_min_nodes,
          f"{nodes} nodes shard evenly over {n_dev} devices, above the gate")
    rng = np.random.default_rng(7)
    avail, total, demand, counts, accel_node, accel_class = build_problem(
        rng, num_tasks=num_tasks, C=classes, N=nodes, R=8)

    class Spec:
        def __init__(self, cpu, cls):
            self.resources = ResourceRequest({"CPU": cpu})
            self.scheduling_options = SchedulingOptions.hybrid()
            self.scheduling_class = cls

    view = ClusterResourceView()
    for i in range(nodes):
        view.add_node(f"n{i:05d}", NodeResources(
            {"CPU": float(4 + 4 * (i % 3)), "memory": 16.0}))
    specs = [Spec(float(1 + i % 4), 5000 + i % 4) for i in range(tick_specs)]

    prev = cfg.solver_shard_backend
    results = {}
    try:
        for mode in ("auto", "off"):
            cfg.solver_shard_backend = mode
            batch = jb.BatchSolver()
            alloc = batch.solve_matrices(avail, total, demand, counts,
                                         accel_node, accel_class,
                                         spread_threshold=0.5)
            live = jb.DeviceRuntimeSolver()
            targets = live.solve(view, specs)
            check(targets is not None and live.stats["device_errors"] == 0
                  and live.stats["fallbacks"] == 0,
                  f"live tick ({mode}) ran clean: {live.stats}")
            results[mode] = (alloc, targets, batch.last_path, live)
    finally:
        cfg.solver_shard_backend = prev
    alloc_sh, targets_sh, path_sh, live_sh = results["auto"]
    alloc_1, targets_1, path_1, live_1 = results["off"]
    check(path_sh == f"sharded[{n_dev}]/jnp" and path_1.startswith("single/"),
          f"solve paths: {path_sh} vs {path_1}")
    check(np.array_equal(alloc_sh, alloc_1),
          "solve_matrices: sharded == single-device")
    check(targets_sh == targets_1, "live tick: sharded == single-device")
    check(live_sh.stats["sharded_ticks"] > 0
          and live_1.stats["sharded_ticks"] == 0, "sharded_ticks counted")
    shard_devs = {s.device for s in live_sh._state["avail_t"].addressable_shards}
    check(len(shard_devs) == n_dev
          and {d.platform for d in shard_devs} == {platform},
          f"avail_t shards on {n_dev} distinct {platform} devices: "
          f"{sorted(d.id for d in shard_devs)}")
    return {"devices": n_dev, "nodes": nodes, "classes": classes,
            "paths": [path_sh, path_1, live_sh.last_path, live_1.last_path],
            "placed": int(alloc_sh.sum()),
            "tick_placed": sum(t is not None for t in targets_sh),
            "sharded_equals_single": True}


def _model_parallel_func(config: dict) -> dict:
    import __graft_entry__ as graft
    return graft.dryrun_multichip(config["devices"])


def leg_model_parallel(platform: str = "tpu", devices: int = None) -> dict:
    """One Trainer worker holding every chip: the dp/sp/tp train step
    (ring attention over sp), then the ep and pp programs, on the real
    devices; parameter shards on every one of them."""
    import jax

    import ray_tpu
    from ray_tpu.train import Trainer

    devices = devices or len(jax.devices())
    check(devices > 1, "model parallelism needs more than one device")
    ray_tpu.init(num_cpus=4, num_tpus=devices)
    try:
        trainer = Trainer(backend="jax", num_workers=1,
                          resources_per_worker={"TPU": devices})
        try:
            (facts,) = trainer.run(_model_parallel_func,
                                   config=dict(devices=devices))
        finally:
            trainer.shutdown()
    finally:
        ray_tpu.shutdown()
    check(facts["platform"] == platform, f"ran on {facts['platform']}")
    check(len(set(facts["param_devices"])) == devices,
          f"parameter shards on {devices} distinct devices: "
          f"{facts['param_devices']}")
    for key in ("loss", "moe_loss", "pp_loss"):
        check(np.isfinite(facts[key]) and facts[key] > 0,
              f"{key} finite: {facts[key]}")
    return facts


# ---------------------------------------------------------------------------

def run_leg(name, clock, fn, **kwargs):
    before = clock.snapshot()
    t0 = time.perf_counter()
    facts = fn(**kwargs)
    wall = time.perf_counter() - t0
    after = clock.snapshot()
    compile_s, hits, misses = (after[key] - before[key] for key in (
        "compile_s", "cache_hits", "cache_misses"))
    # Compile seconds are summed over threads (raylets compile side by
    # side), so "run" is what is left of the wall clock at least.
    print(f"leg {name}: PASSED  wall {wall:.1f}s; compile {compile_s:.1f}s "
          f"(persistent cache: {hits} hits, {misses} misses); run "
          f"{max(wall - compile_s, 0.0):.1f}s  "
          f"[set-up facts, not benchmark numbers]")
    print(f"  {json.dumps(facts)}", flush=True)
    return facts


def main() -> int:
    import jax

    from benchmarks.harness.compile_clock import clock as compile_clock
    from ray_tpu._private.device_policy import enable_compile_cache
    cache_dir = enable_compile_cache()
    device = device_facts()
    if device["platform"] != "tpu":
        print(f"chip_smoke: no TPU: jax.devices()[0].platform is "
              f"{device['platform']!r}; this script only runs on the chip",
              file=sys.stderr)
        return 1
    print(f"chip_smoke: platform={device['platform']} "
          f"device_kind={device['kind']!r} count={device['count']} "
          f"python={sys.version.split()[0]} jax={jax.__version__} "
          f"jaxlib={_version('jaxlib')} libtpu={_version('libtpu')}")
    entries_before = len(os.listdir(cache_dir)) \
        if os.path.isdir(cache_dir) else 0
    print(f"compile cache: {cache_dir} ({entries_before} entries at start)",
          flush=True)

    clock = compile_clock()
    run_leg("1 live runtime", clock, leg_live_runtime)
    run_leg("2 scheduler kernel 1M x 256 x 10k", clock, leg_scheduler_kernel)
    run_leg("3 trainer 200M x 8 x 1024", clock, leg_trainer)
    run_leg("3b latent attention + experts + MTP 182M x 4 x 1024", clock,
            leg_latent_trainer)
    run_leg("3c delta rule + gated attention + experts 132M x 4 x 1024",
            clock, leg_hybrid_trainer)
    run_leg("3d selective scan + differential attention 180M x 1 x 2048",
            clock, leg_sambay_trainer)
    if device["count"] > 1:
        run_leg("4 sharded solve", clock, leg_sharded_solve)
        run_leg("5 model parallel dp/sp/tp + ep + pp", clock,
                leg_model_parallel)
    else:
        print("leg 4 sharded solve: NOT RUN (one device visible)")
        print("leg 5 model parallel: NOT RUN (one device visible)")
    total = clock.snapshot()
    print(f"compile cache: {cache_dir} ({len(os.listdir(cache_dir))} entries "
          f"at end, {entries_before} at start); this run: "
          f"{total['cache_hits']} hits, {total['cache_misses']} misses, "
          f"{total['compile_s']:.1f}s tracing+lowering+compiling")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
