"""Model + parallelism tests on the virtual 8-device CPU mesh:
ring attention vs full attention, sharded train step, graft entry."""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

_GRAFT_ENTRY = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            os.pardir, "__graft_entry__.py")


def test_ring_attention_matches_full():

    from ray_tpu.ops.ring_attention import full_attention, ring_attention
    from ray_tpu.parallel.mesh import MeshConfig, build_mesh

    mesh = build_mesh(MeshConfig(sp=4), devices=jax.devices()[:4])
    B, S, H, D = 2, 64, 4, 16
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.float32)

    want = full_attention(q, k, v)
    fn = jax.shard_map(
        lambda q, k, v: ring_attention(q, k, v, axis_name="sp", causal=True),
        mesh=mesh,
        in_specs=(P(None, "sp", None, None),) * 3,
        out_specs=P(None, "sp", None, None),
        check_vma=False)
    with mesh:
        got = jax.jit(fn)(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_ring_attention_non_causal():

    from ray_tpu.ops.ring_attention import full_attention, ring_attention
    from ray_tpu.parallel.mesh import MeshConfig, build_mesh

    mesh = build_mesh(MeshConfig(sp=8), devices=jax.devices()[:8])
    B, S, H, D = 1, 128, 2, 8
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.float32)
    from ray_tpu.ops.attention_mask import FULL
    want = full_attention(q, k, v, mask=FULL)
    fn = jax.shard_map(
        lambda q, k, v: ring_attention(q, k, v, axis_name="sp",
                                       causal=False),
        mesh=mesh, in_specs=(P(None, "sp", None, None),) * 3,
        out_specs=P(None, "sp", None, None), check_vma=False)
    with mesh:
        got = jax.jit(fn)(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_forward_shapes_single_device():
    from ray_tpu.models.transformer import (
        TransformerConfig, forward, init_params)
    cfg = TransformerConfig(vocab_size=64, d_model=32, n_layers=2,
                            n_heads=2, d_ff=64, dtype=jnp.float32,
                            remat=False)
    params = init_params(jax.random.PRNGKey(0), cfg)
    tokens = jnp.zeros((2, 16), jnp.int32)
    logits = jax.jit(lambda p, t: forward(p, t, cfg))(params, tokens)
    assert logits.shape == (2, 16, 64)
    assert bool(jnp.isfinite(logits).all())


def test_train_step_loss_decreases():
    from ray_tpu.models.transformer import (
        TransformerConfig, make_train_state, make_train_step)
    cfg = TransformerConfig(vocab_size=32, d_model=32, n_layers=1,
                            n_heads=2, d_ff=64, dtype=jnp.float32,
                            remat=False)
    state, tx = make_train_state(jax.random.PRNGKey(0), cfg,
                                 learning_rate=1e-2)
    step = make_train_step(cfg, tx)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 17), 0, 32,
                                dtype=jnp.int32)
    batch = {"tokens": tokens}
    state, m0 = step(state, batch)
    for _ in range(10):
        state, m = step(state, batch)
    assert float(m["loss"]) < float(m0["loss"])


def test_sharded_train_step_matches_single_device():
    """The dp x tp sharded step computes the same loss as single-device."""
    from ray_tpu.models.transformer import (
        TransformerConfig, loss_fn, make_train_state)
    from ray_tpu.parallel.mesh import MeshConfig, build_mesh
    cfg = TransformerConfig(vocab_size=64, d_model=32, n_layers=2,
                            n_heads=4, d_ff=64, dtype=jnp.float32,
                            remat=False, context_parallel=False)
    mesh = build_mesh(MeshConfig(dp=2, tp=4), devices=jax.devices()[:8])
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 33), 0, 64,
                                dtype=jnp.int32)
    state_plain, _ = make_train_state(jax.random.PRNGKey(0), cfg)
    want = float(jax.jit(
        lambda p: loss_fn(p, {"tokens": tokens}, cfg))(state_plain["params"]))
    with mesh:
        state_sharded, _ = make_train_state(jax.random.PRNGKey(0), cfg,
                                            mesh=mesh)
        got = float(jax.jit(
            lambda p: loss_fn(p, {"tokens": tokens}, cfg, mesh))(
                state_sharded["params"]))
    assert abs(got - want) < 1e-3, (got, want)


def test_graft_entry_single_chip():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "graft_entry", _GRAFT_ENTRY)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    fn, args = mod.entry()
    out = jax.jit(fn)(*args)
    assert out.shape[0] == 2 and out.ndim == 3


def test_graft_entry_dryrun_multichip():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "graft_entry", _GRAFT_ENTRY)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    mod.dryrun_multichip(8)


def test_moe_expert_parallel_train_step():
    """The one expert layer with its experts sharded over the ep axis
    (each shard runs the layer on its own range, the partial results
    are summed with psum): the sharded loss matches the unsharded one,
    a train step is finite and reports the layer's counters, nothing is
    dropped, routing uses several experts, and the router's
    load-balance auxiliary is in the loss with the same gradient
    sharded as not."""
    import numpy as np

    from ray_tpu.models.transformer import (
        TransformerConfig, loss_and_counters, loss_fn, make_train_state,
        make_train_step)
    from ray_tpu.parallel.mesh import MeshConfig, build_mesh
    cfg = TransformerConfig(vocab_size=64, d_model=32, n_layers=2,
                            n_heads=4, d_ff=64, dtype=jnp.float32,
                            remat=False, context_parallel=False,
                            moe_experts=4, moe_top_k=2)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 33), 0, 64,
                                dtype=jnp.int32)
    state_plain, _ = make_train_state(jax.random.PRNGKey(0), cfg)
    want, plain = jax.jit(
        lambda p: loss_and_counters(p, {"tokens": tokens}, cfg))(
            state_plain["params"])
    # 4 rows x 32 positions x 2 choices, all four experts held
    assert float(plain["moe_held_choices"]) == 4 * 32 * 2
    assert float(plain["moe_dropped_choices"]) == 0.0
    assert 0.25 <= float(plain["moe_expert_load_max"]) < 1.0  # > 1 expert
    # the auxiliary (1 at balance) is in the loss at its default weight
    assert float(plain["moe_balance_loss"]) >= 1.0
    without = dataclasses.replace(cfg, moe_aux_coeff=0.0)
    assert float(want) - float(loss_fn(
        state_plain["params"], {"tokens": tokens}, without)) == \
        pytest.approx(0.01 * float(plain["moe_balance_loss"]), rel=1e-3)
    want_grad = jax.jit(jax.grad(
        lambda p: loss_fn(p, {"tokens": tokens}, cfg)))(
            state_plain["params"])
    mesh = build_mesh(MeshConfig(dp=2, ep=4), devices=jax.devices()[:8])
    with mesh:
        state, tx = make_train_state(jax.random.PRNGKey(0), cfg,
                                     mesh=mesh)
        got = float(jax.jit(
            lambda p: loss_fn(p, {"tokens": tokens}, cfg, mesh))(
                state["params"]))
        assert abs(got - float(want)) < 1e-3, (got, want)
        got_grad = jax.jit(jax.grad(
            lambda p: loss_fn(p, {"tokens": tokens}, cfg, mesh)))(
                state["params"])
        for name in ("wr", "w1", "w2"):
            g, w = (np.asarray(t["layers"]["moe"][name])
                    for t in (got_grad, want_grad))
            assert np.abs(g - w).max() <= 1e-4 * np.abs(w).max() + 1e-7, name
        step = make_train_step(cfg, tx, mesh=mesh)
        state, metrics = step(state, {"tokens": tokens})
        assert np.isfinite(float(metrics["loss"]))
        assert float(metrics["grad_norm"]) > 0
        assert float(metrics["moe_held_choices"]) == 4 * 32 * 2
        assert float(metrics["moe_dropped_choices"]) == 0.0
        assert float(metrics["moe_expert_load_max"]) == pytest.approx(
            float(plain["moe_expert_load_max"]))
        assert float(metrics["moe_balance_loss"]) == pytest.approx(
            float(plain["moe_balance_loss"]), rel=1e-5)


def test_mixed_stack_latent_attention_and_shared_expert_on_the_mesh():
    """The layer pattern (a dense-FFN layer, expert layers, the
    multi-token-prediction module) with latent attention's heads over
    ``tp`` and the experts over ``ep``: loss, gradients, the step's
    counters and the routers' correction bias equal the unsharded
    step's.  The shared expert is outside the shards' ``psum``: counted
    once, not once a shard (leaving it inside would double it here)."""
    import functools

    import numpy as np

    from ray_tpu.models import mtp
    from ray_tpu.models.mla import MLAConfig
    from ray_tpu.models.transformer import (TransformerConfig,
                                            make_train_state,
                                            make_train_step)
    from ray_tpu.parallel.mesh import MeshConfig, build_mesh
    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_heads=4, d_ff=48, dtype=jnp.float32,
        remat=True, context_parallel=False, norm_eps=1e-6,
        mla=MLAConfig(24, 16, 8, 4, 8),
        layer_pattern=(("mla", "dense", 1), ("mla", "moe", 2)), mtp_depth=1,
        moe_experts=8, moe_top_k=2, moe_d_ff=16, moe_scoring="sigmoid",
        moe_route_scale=2.5, moe_shared_width=16, moe_bias_rate=1e-3,
        moe_aux_coeff=0.0)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 33), 0, 64,
                                dtype=jnp.int32)
    objective = functools.partial(mtp.loss_fn, cfg=cfg, coeff=0.3)
    plain_state, plain_tx = make_train_state(jax.random.PRNGKey(0), cfg)
    bias = plain_state["moe_bias"] + 0.01 * jnp.arange(8)
    want, want_grad = jax.jit(jax.value_and_grad(
        lambda p: objective(p, {"tokens": tokens}, bias)[0]))(
            plain_state["params"])
    after, plain = make_train_step(cfg, plain_tx, loss_override=objective)(
        dict(plain_state, moe_bias=bias + 0.0), {"tokens": tokens})
    mesh = build_mesh(MeshConfig(dp=2, tp=2, ep=2), devices=jax.devices()[:8])
    with mesh:
        state, tx = make_train_state(jax.random.PRNGKey(0), cfg, mesh=mesh)
        shards = {name: state["params"]["layers"][1][group][name].sharding.spec
                  for group, name in (("mla", "wq_b"), ("mla", "wq_a"),
                                      ("moe", "ws1"), ("moe", "w1"))}
        assert shards["wq_b"] == jax.sharding.PartitionSpec(
            None, None, "tp", None)
        assert "tp" not in shards["wq_a"] and "ep" in shards["w1"]
        assert "tp" in shards["ws1"] and "ep" not in shards["ws1"]
        sharded = functools.partial(objective, mesh=mesh)
        got, got_grad = jax.jit(jax.value_and_grad(
            lambda p: sharded(p, {"tokens": tokens}, bias)[0]))(
                state["params"])
        assert abs(float(got) - float(want)) < 1e-4, (got, want)
        for (path, g), w in zip(
                jax.tree_util.tree_flatten_with_path(got_grad)[0],
                jax.tree.leaves(want_grad)):
            g, w = np.asarray(g), np.asarray(w)
            assert np.abs(g - w).max() <= 1e-4 * np.abs(w).max() + 1e-7, path
        step = make_train_step(cfg, tx, mesh=mesh, loss_override=sharded)
        state, metrics = step(dict(state, moe_bias=bias + 0.0),
                              {"tokens": tokens})
        for name in ("loss", "main_loss", "mtp_loss", "moe_held_choices",
                     "moe_load_cv", "moe_bias_abs_max"):
            assert float(metrics[name]) == pytest.approx(
                float(plain[name]), rel=1e-4), name
        # the loads are summed over the data axes: the same bias
        assert np.array_equal(np.asarray(state["moe_bias"]),
                              np.asarray(after["moe_bias"]))
        # all 8 experts are held between the shards: every choice counts
        assert float(metrics["moe_held_choices"]) == 4 * 32 * 2
        assert float(metrics["moe_dropped_choices"]) == 0.0


def test_hybrid_stack_delta_layers_and_gated_shared_expert_on_the_mesh():
    """Two periods of (delta layer, gated-attention layer) with the
    delta layers' key heads -- and the value heads, convolution taps,
    decays and output rows that belong to them -- over ``tp`` and the
    experts over ``ep``: loss, gradients and the step's counters equal
    the unsharded step's.  The gated shared expert is outside the
    shards' ``psum``: counted once.  Over ``sp`` a delta layer raises."""
    import dataclasses

    import numpy as np

    from ray_tpu.models.gdn import GDNConfig
    from ray_tpu.models.transformer import (TransformerConfig,
                                            loss_and_counters,
                                            make_train_state,
                                            make_train_step)
    from ray_tpu.parallel.mesh import MeshConfig, build_mesh
    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_heads=4, n_kv_heads=2, head_dim=16,
        d_ff=16, dtype=jnp.float32, remat=True, context_parallel=False,
        norm_eps=1e-6, qk_norm=True, norm_plus_one=True, attn_out_gate=True,
        rotary_dim=4, gdn=GDNConfig(2, 4, 8, 8, 4, 16),
        layer_pattern=(((("gdn", "moe", 1), ("mha", "moe", 1)), 2),),
        moe_experts=8, moe_top_k=2, moe_shared_width=16,
        moe_shared_gate=True, moe_aux_coeff=0.001)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 33), 0, 64,
                                dtype=jnp.int32)
    batch = {"tokens": tokens}
    plain_state, plain_tx = make_train_state(jax.random.PRNGKey(0), cfg)
    want, want_grad = jax.jit(jax.value_and_grad(
        lambda p: loss_and_counters(p, batch, cfg)[0]))(plain_state["params"])
    over_sp = dataclasses.replace(cfg, context_parallel=True)
    ring = build_mesh(MeshConfig(dp=2, sp=2), devices=jax.devices()[:4])
    with ring, pytest.raises(ValueError, match="sp axis"):
        loss_and_counters(plain_state["params"], batch, over_sp, ring)
    _, plain = make_train_step(cfg, plain_tx)(plain_state, batch)
    mesh = build_mesh(MeshConfig(dp=2, tp=2, ep=2), devices=jax.devices()[:8])
    with mesh:
        state, tx = make_train_state(jax.random.PRNGKey(0), cfg, mesh=mesh)
        delta, attention = state["params"]["layers"][0]
        spec = jax.sharding.PartitionSpec
        assert delta["gdn"]["w_qkvz"].sharding.spec == spec(
            None, None, None, "tp", None)
        assert delta["gdn"]["A_log"].sharding.spec == spec(
            None, None, "tp", None)
        assert delta["gdn"]["wo"].sharding.spec == spec(
            None, None, "tp", None, None)
        assert "tp" not in delta["gdn"]["norm"].sharding.spec
        assert "ep" in delta["moe"]["w1"].sharding.spec
        assert not any(delta["moe"]["wsg"].sharding.spec)
        assert "tp" in attention["wq"].sharding.spec
        got, got_grad = jax.jit(jax.value_and_grad(
            lambda p: loss_and_counters(p, batch, cfg, mesh)[0]))(
                state["params"])
        assert abs(float(got) - float(want)) < 1e-4, (got, want)
        for (path, g), w in zip(
                jax.tree_util.tree_flatten_with_path(got_grad)[0],
                jax.tree.leaves(want_grad)):
            g, w = np.asarray(g), np.asarray(w)
            assert np.abs(g - w).max() <= 1e-4 * np.abs(w).max() + 1e-7, path
        state, metrics = make_train_step(cfg, tx, mesh=mesh)(state, batch)
        for name in ("loss", "moe_held_choices", "moe_balance_loss",
                     "moe_shared_gate_mean", "attn_gate_mean",
                     "gdn_state_norm", "gdn_decay_mean", "gdn_beta_mean"):
            assert float(metrics[name]) == pytest.approx(
                float(plain[name]), rel=1e-4), name
        # all 8 experts are held between the shards: every choice counts
        assert float(metrics["moe_held_choices"]) == 4 * 32 * 2
        assert float(metrics["moe_dropped_choices"]) == 0.0


def test_pipeline_parallel_matches_single_device():
    """GPipe over pp=2 (x dp=2): the pipelined loss equals the plain
    sequential loss exactly, and a full pp train step (AD through
    ppermute) runs finite."""
    import numpy as np

    from ray_tpu.models.transformer import (
        TransformerConfig, loss_fn, make_train_state)
    from ray_tpu.parallel.mesh import MeshConfig, build_mesh
    from ray_tpu.parallel.pipeline import (make_pp_loss_fn,
                                           make_pp_train_state,
                                           make_pp_train_step)
    cfg = TransformerConfig(vocab_size=64, d_model=32, n_layers=4,
                            n_heads=4, d_ff=64, dtype=jnp.float32,
                            remat=False, context_parallel=False)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 33), 0, 64,
                                dtype=jnp.int32)
    state_plain, _ = make_train_state(jax.random.PRNGKey(0), cfg)
    want = float(jax.jit(
        lambda p: loss_fn(p, {"tokens": tokens}, cfg))(
            state_plain["params"]))

    mesh = build_mesh(MeshConfig(dp=2, pp=2), devices=jax.devices()[:4])
    with mesh:
        state, tx = make_pp_train_state(jax.random.PRNGKey(0), cfg,
                                        mesh)
        pp_loss = make_pp_loss_fn(cfg, mesh, n_micro=2)
        got = float(jax.jit(
            lambda p: pp_loss(p, {"tokens": tokens}))(state["params"]))
        assert abs(got - want) < 1e-3, (got, want)
        step = make_pp_train_step(cfg, tx, mesh, n_micro=2)
        state, metrics = step(state, {"tokens": tokens})
        assert np.isfinite(float(metrics["loss"]))
        state, metrics2 = step(state, {"tokens": tokens})
        assert float(metrics2["loss"]) < float(metrics["loss"]) + 1.0


@pytest.mark.parametrize("attention", ["reference", "kernel"])
def test_pipeline_stage_remat_matches_no_remat(attention, monkeypatch):
    """The stage's scan shares ``remat_layer`` with ``run_layers``:
    ``remat=True`` -- keeping the flash kernel's residuals, where the
    kernel runs (interpreted here), and under a plan with room for them
    the layer's named products too (``models/remat.py``) -- gives the
    loss and the gradients of ``remat=False`` over pp=2 x dp=2.  Every
    tick of the schedule keeps its stage's stacks: three ticks of two
    layers are three runs of two, and under the plan the kept products
    are named once a tick (the forward), under today's twice."""
    import functools

    from ray_tpu.models import mha, remat as remat_plan, transformer
    from ray_tpu.ops.flash_attention import flash_attention
    from ray_tpu.parallel.mesh import MeshConfig, build_mesh
    from ray_tpu.parallel.pipeline import (make_pp_loss_fn,
                                           make_pp_train_state)
    if attention == "kernel":
        monkeypatch.setattr(mha, "flash_or_ref_attention",
                            functools.partial(flash_attention,
                                              interpret=True))
    # (at this width no product is dearer to make again than to keep)
    monkeypatch.setattr(remat_plan, "_KEPT_BYTE_MOVES", 0.0)
    base = transformer.TransformerConfig(
        vocab_size=64, d_model=32, n_layers=4, n_heads=2, d_ff=64,
        max_seq_len=128, dtype=jnp.float32, context_parallel=False)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 129), 0, 64,
                                dtype=jnp.int32)
    mesh = build_mesh(MeshConfig(dp=2, pp=2), devices=jax.devices()[:4])
    got, named, plans = {}, {}, []
    with mesh:
        for how in ("planned", "today", "no remat"):
            cfg = dataclasses.replace(base, remat=how != "no remat")
            state, _ = make_pp_train_state(jax.random.PRNGKey(0), cfg, mesh)
            pp_loss = make_pp_loss_fn(cfg, mesh, n_micro=2)
            monkeypatch.setattr(
                remat_plan, "device_memory", lambda mesh=None, how=how: (
                    (1 << 40, 0) if how == "planned" else None))

            def fn(p):
                ((loss, _), grads), plan = remat_plan.value_and_grad(
                    lambda p: (pp_loss(p, {"tokens": tokens}), {}), p)
                plans.append(plan)
                return loss, grads

            text = str(jax.make_jaxpr(fn)(state["params"]))
            assert ("name=flash_attention_fwd" in text) == (
                attention == "kernel")
            got[how] = jax.jit(fn)(state["params"])
            named[how] = text.count("name=attn_q]"), text.count(
                "name=mid_residual]")
    runs = plans[0]["runs"]
    # (inside the shard_map: a device's shapes as they are)
    assert [(r["layers"], r["kind"]) for r in runs] == [(2, "mha+dense")] * 3
    assert all({"attn_q", "attn_k", "attn_v", "mid_residual"}
               <= set(r["names"]) for r in runs)
    assert named == {"planned": (3, 3), "today": (6, 6), "no remat": (3, 3)}
    for other in ("today", "no remat"):
        assert float(got["planned"][0]) == pytest.approx(
            float(got[other][0]), rel=1e-6)
        for a, b in zip(jax.tree.leaves(got["planned"][1]),
                        jax.tree.leaves(got[other][1])):
            assert bool(jnp.all(jnp.isfinite(a)))
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-7)
