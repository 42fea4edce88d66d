"""The first mixed layer stack: latent attention (``models/mla.py``),
the sigmoid router with its correction bias and the shared expert
(``models/moe.py``), the layer pattern (``models/transformer.py``) and
the multi-token-prediction module (``models/mtp.py``), at a tiny size
against the benchmark's plain reference
(``benchmarks/reference/mla_moe_mtp.py``)."""

import dataclasses
import functools
import os
import pickle
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from ray_tpu.models import moe, mtp  # noqa: E402
from ray_tpu.models.mla import MLAConfig  # noqa: E402
from ray_tpu.models.transformer import (  # noqa: E402
    TransformerConfig, init_params, loss_and_counters, make_train_state,
    make_train_step, param_specs)

# 1 dense-FFN layer + 2 expert layers + the module; 16 experts of which
# this rank holds 4 (experts 4-7), 4 a token; 4 heads scoring over 16 + 8
# columns, values over 16.
CONFIG = {
    "reference": "mla_moe_mtp", "hidden_size": 64, "intermediate_size": 96,
    "moe_intermediate_size": 32, "num_attention_heads": 4,
    "q_lora_rank": 48, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "rope_interleave": True,
    "rope_theta": 32000000, "first_k_dense_replace": 1,
    "num_hidden_layers": 3, "num_nextn_predict_layers": 1, "vocab_size": 128,
    "n_routed_experts": 16, "num_experts_per_tok": 4,
    "n_routed_experts_held": 4, "experts_held_first": 4,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
    "bias_update_rate": 0.001, "mtp_loss_coef": 0.3,
    "dispatch_alike_tail": 0.001, "rms_norm_eps": 1e-6,
    "initializer_range": 0.02, "dtype": "float32", "remat": True,
    "optimizer": {"name": "adamw", "learning_rate": 3e-4, "b1": 0.9,
                  "b2": 0.95, "eps": 1e-8, "weight_decay": 0.1},
}
TRAFFIC = {"kind": "packed_documents", "rows": 2, "seq_len": 32,
           "pool_batches": 2, "doc_len": {"alpha": 1.2, "min": 4, "max": 64},
           "bos_id": 0}
CELL = {"check": {"steps": 2}}
# float32 on both sides, the reference following the program's experts:
# summation order.  The fp8 control reads tens of times these.
LIMITS = {"grad1_norm_gap": 1e-3, "change_norm_gap": 2e-3}
LOSS_GAP = 1e-5


def _cfg(**changes):
    from benchmarks.drivers import trainer_mla_mtp_steps as driver
    kwargs = driver._model_kwargs(CONFIG, TRAFFIC["seq_len"])
    kwargs["mla"] = MLAConfig(**kwargs["mla"])
    return TransformerConfig(dtype=jnp.float32, **dict(kwargs, **changes))


def _batches(seed):
    from benchmarks.harness import traffic
    return list(traffic.generate(TRAFFIC, seed, vocab_size=128))


def _program(seed, batches):
    """Two steps of ``make_train_step`` under the multi-token objective
    from the seed's weights -> what the reference returns."""
    from benchmarks.drivers import trainer_mla_mtp_steps as driver
    from benchmarks.drivers.trainer_steps import _adam_mu
    from benchmarks.harness import mla_weights
    cfg = _cfg()
    state, tx = make_train_state(
        jax.random.PRNGKey(0), cfg,
        learning_rate=CONFIG["optimizer"]["learning_rate"])
    start = mla_weights.make_latent_moe(seed, CONFIG, jnp.float32)
    assert jax.tree.map(jnp.shape, start) == jax.tree.map(
        jnp.shape, state["params"])
    state["params"] = start
    step = make_train_step(cfg, tx, loss_override=functools.partial(
        mtp.loss_fn, cfg=cfg, coeff=CONFIG["mtp_loss_coef"]))
    out = {"losses": [], "metrics": [], "choices": []}
    for i, batch in enumerate(batches):
        state, metrics = step(state, {"tokens": jnp.asarray(batch)})
        out["losses"].append(float(metrics["loss"]))
        out["choices"].append(np.asarray(metrics.pop("moe_choices")))
        out["metrics"].append({k: float(v) for k, v in metrics.items()})
        if i == 0:
            out["grad1_norm"] = {
                k: np.asarray(v, np.float64) / (1.0 - 0.9) for k, v in
                driver.leaf_norms(_adam_mu(state["opt"])).items()}
    again = mla_weights.make_latent_moe(seed, CONFIG, jnp.float32)
    out["change_norm"] = {k: np.asarray(v, np.float64) for k, v in
                          driver.leaf_norms(jax.tree.map(
                              lambda a, b: a - b, state["params"],
                              again)).items()}
    out["moe_bias"] = np.asarray(state["moe_bias"])
    return out


def test_program_matches_the_plain_reference_and_the_controls_do_not():
    """(b) The whole loss, the first gradient leaf by leaf, the
    parameters' change over two AdamW steps and the correction bias
    after them."""
    from benchmarks.drivers import trainer_mla_mtp_steps as driver
    from benchmarks.harness import compare
    seed = 2**31 + 5
    batches = _batches(seed)
    prog = _program(seed, batches)
    ref = driver.follow_reference(CELL, CONFIG, seed, batches,
                                  choices=prog["choices"])
    # 2 expert layers and the module's, 2 rows x 32 positions, 4 choices
    assert prog["choices"][0].shape == (3, 2, 32, 4)
    # the program's experts are the reference's own, or tied with them
    assert ref["routing_gap"][0] <= 1e-6, ref["routing_gap"]
    assert max(compare.loss_gaps(prog, ref)) <= LOSS_GAP
    correct, compared = compare.judge(compare.train_numbers(prog, ref),
                                      LIMITS)
    assert correct, compared
    # every leaf of every kind of layer is among the compared
    leaves = set(prog["grad1_norm"])
    assert {"layers.0.w1", "layers.0.mla.wq_a", "layers.1.moe.ws2",
            "layers.1.mla.wkv_b", "mtp.w_eh", "mtp.layers.moe.wr",
            "mtp.ln_f", "embed", "lm_head"} <= leaves
    assert prog["grad1_norm"]["layers.1.moe.wr"].shape == (2,)
    # the bias: signs of whole numbers, so equal exactly; it has moved
    assert prog["moe_bias"].shape == (3, 16)
    assert np.array_equal(prog["moe_bias"], ref["moe_bias"])
    assert set(np.unique(np.abs(prog["moe_bias"]))) <= {
        np.float32(0.0), np.float32(0.001), np.float32(0.001) * 2}
    for metrics, (main, extra) in zip(prog["metrics"], ref["loss_parts"]):
        assert metrics["moe_dropped_choices"] == 0.0
        assert 0 < metrics["moe_held_choices"] < 2 * 32 * 4
        assert metrics["main_loss"] == pytest.approx(main, rel=1e-5)
        assert metrics["mtp_loss"] == pytest.approx(extra, rel=1e-5)
        assert metrics["loss"] == pytest.approx(
            metrics["main_loss"] + 0.3 * metrics["mtp_loss"], rel=1e-6)
        assert metrics["moe_load_cv"] > 0
    assert [m["moe_bias_abs_max"] for m in prog["metrics"]] == [
        pytest.approx(0.001), pytest.approx(0.002)]
    # each control in the program's place, its experts followed likewise
    for how in (dict(precision="fp8"), dict(rotary=False),
                dict(scoring="softmax"), dict(mtp_coeff=0.0),
                dict(shared=False)):
        control = driver.follow_reference(CELL, CONFIG, seed, batches, **how)
        ref = driver.follow_reference(CELL, CONFIG, seed, batches,
                                      choices=control["choices"])
        correct, compared = compare.judge(
            compare.train_numbers(control, ref), LIMITS)
        assert not correct, (how, compared)


def test_the_shares_add_up_to_the_uncut_layer():
    """(c) The four shares of four experts each, the shared expert
    counted once, sum to what the plain reference gives for the whole
    expert layer with all sixteen experts."""
    from benchmarks.reference import mla_moe_mtp as reference
    cfg = _cfg(moe_experts_held=None)
    lp = jax.tree.map(lambda a: a[0], init_params(
        jax.random.PRNGKey(7), cfg)["layers"][1]["moe"])
    # a router that spreads: its initial weights barely tell experts apart
    lp["wr"] = lp["wr"] * 40.0
    bias = jnp.linspace(-0.05, 0.05, 16)
    h = jax.random.normal(jax.random.PRNGKey(8), (2, 32, 64), jnp.float32)
    router = dict(scoring="sigmoid", route_scale=2.5)
    total = moe.shared_expert(h, lp)
    seen = []
    for first in range(0, 16, 4):
        share = dict(lp, bias=bias, **{k: lp[k][first:first + 4]
                                       for k in ("w1", "w3", "w2")})
        y, stats = moe.moe_ffn(h, share, 4, True, held=(first, 4), **router)
        assert int(stats["dropped_choices"]) == 0
        total = total + y
        seen.append(int(stats["held_choices"]))
    # every choice lands on exactly one share
    assert sum(seen) == 2 * 32 * 4 and min(seen) > 0
    hp = {"eps": 1e-6, "top_k": 4, "norm_topk": True, "route_scale": 2.5,
          "scoring": "sigmoid", "first": 0, "shared": True}
    flat = {"moe." + k: v for k, v in lp.items()}
    for r in range(2):
        want, chosen, _ = reference._experts(flat, h[r], bias, hp, "float32",
                                             None)
        assert float(jnp.max(jnp.abs(total[r] - want))) <= 2e-5
        # and the shared expert alone is not nothing
        assert float(jnp.max(jnp.abs(moe.shared_expert(h, lp)[r]))) > 1e-3


def test_without_its_weight_the_module_changes_nothing_it_shares():
    """lambda = 0: the embedding, the head and every layer of the stack
    leave a step as they leave the step of the model without the
    module."""
    batch = {"tokens": jnp.asarray(_batches(3)[0])}
    with_module = _cfg()
    without = _cfg(mtp_depth=0)
    state, tx = make_train_state(jax.random.PRNGKey(1), with_module)
    bare, tx_bare = make_train_state(jax.random.PRNGKey(1), without)
    shared = {k: v for k, v in state["params"].items() if k != "mtp"}
    assert jax.tree.structure(shared) == jax.tree.structure(bare["params"])
    bare["params"] = jax.tree.map(jnp.copy, shared)
    state, metrics = make_train_step(
        with_module, tx, loss_override=functools.partial(
            mtp.loss_fn, cfg=with_module, coeff=0.0))(state, batch)
    bare, plain = make_train_step(without, tx_bare)(bare, batch)
    assert float(metrics["loss"]) == float(metrics["main_loss"]) \
        == pytest.approx(float(plain["loss"]), rel=1e-6)
    assert float(metrics["mtp_loss"]) > 0
    for (path, a), b in zip(
            jax.tree_util.tree_flatten_with_path(
                {k: v for k, v in state["params"].items() if k != "mtp"})[0],
            jax.tree.leaves(bare["params"])):
        # two compiled programs: a hundredth of the 3e-4 an Adam step moves
        assert float(jnp.max(jnp.abs(a - b))) <= 3e-6, path
    # the stack's bias rows moved alike; the module's row is its own
    assert np.array_equal(np.asarray(state["moe_bias"][:2]),
                          np.asarray(bare["moe_bias"]))


def test_the_bias_gets_no_gradient_no_decay_and_no_moments(tmp_path):
    """It is state beside the parameters: ``tx`` never sees it, the
    loss's gradient by it is nought, the step moves it by the rule
    alone, and a checkpoint round-trips it."""
    from ray_tpu.train.checkpoint import CheckpointManager
    cfg = _cfg()
    state, tx = make_train_state(jax.random.PRNGKey(1), cfg)
    assert state["moe_bias"].shape == (3, 16)
    assert state["moe_bias"].dtype == jnp.float32
    n_params = len(jax.tree.leaves(state["params"]))
    moments = [e for e in state["opt"] if hasattr(e, "mu")][0]
    assert len(jax.tree.leaves(moments.mu)) == n_params
    assert "bias" not in str(jax.tree.structure(state["params"]))
    batch = {"tokens": jnp.asarray(_batches(3)[0])}
    bias = jnp.full((3, 16), 0.01) * jnp.arange(16)
    by_bias = jax.grad(lambda b: mtp.loss_fn(
        state["params"], batch, b, cfg=cfg, coeff=0.3)[0])(bias)
    assert float(jnp.max(jnp.abs(by_bias))) == 0.0
    # ... though it chooses: another bias, other experts, another loss
    assert float(mtp.loss_fn(state["params"], batch, bias, cfg=cfg,
                             coeff=0.3)[0]) != float(mtp.loss_fn(
                                 state["params"], batch, 0 * bias, cfg=cfg,
                                 coeff=0.3)[0])
    step = make_train_step(cfg, tx, loss_override=functools.partial(
        mtp.loss_fn, cfg=cfg, coeff=0.3))
    before = np.asarray(bias)              # the step donates its state
    new, metrics = step(dict(state, moe_bias=bias), batch)
    moved = np.asarray(new["moe_bias"]) - before
    # by the rate, up or down, whatever its size (no decay)
    assert {round(float(m), 6) for m in np.unique(moved)} <= {
        -0.001, 0.0, 0.001}
    assert "moe_router_load" not in metrics
    # the rule, on counts worked by hand: mean load 2
    assert np.array_equal(
        np.asarray(moe.update_bias(jnp.zeros((1, 4)),
                                   jnp.array([[5, 2, 1, 0]]), 0.5)),
        [[-0.5, 0.0, 0.5, 0.5]])
    manager = CheckpointManager(str(tmp_path))
    path = manager.process_checkpoint(jax.device_get(new))
    back = CheckpointManager.load(path)
    assert np.array_equal(back["moe_bias"], np.asarray(new["moe_bias"]))
    assert pickle.dumps(jax.tree.structure(back)) == pickle.dumps(
        jax.tree.structure(jax.device_get(new)))


def test_the_layer_pattern_names_what_a_model_is_made_of():
    """One stack is the tree it always was; several are a tuple, each
    with its kind's leaves and specs; the defaults are one run."""
    dense = TransformerConfig()
    assert dense.layer_pattern == (("mha", "dense", 4),)
    assert isinstance(init_params(jax.random.PRNGKey(0), dataclasses.replace(
        dense, vocab_size=64, d_model=32, d_ff=48))["layers"], dict)
    cfg = _cfg()
    assert cfg.n_layers == 3 and cfg.moe_layers == 3
    params = init_params(jax.random.PRNGKey(0), cfg)
    first, rest = params["layers"]
    assert set(first) == {"ln1", "ln2", "mla", "w1", "w3", "w2"}
    assert set(rest) == {"ln1", "ln2", "mla", "moe"}
    assert set(rest["moe"]) == {"wr", "w1", "w3", "w2", "ws1", "ws3", "ws2"}
    assert first["w1"].shape == (1, 64, 96)
    assert rest["moe"]["w1"].shape == (2, 4, 64, 32)
    assert rest["mla"]["wq_b"].shape == (2, 48, 4, 24)
    assert rest["mla"]["wkv_a"].shape == (2, 64, 40)
    specs = param_specs(cfg)
    assert jax.tree.structure(
        specs, is_leaf=lambda s: isinstance(s, jax.sharding.PartitionSpec)
    ) == jax.tree.structure(params)
    P = jax.sharding.PartitionSpec
    assert specs["layers"][1]["mla"]["wq_b"] == P(None, None, "tp", None)
    assert specs["layers"][1]["mla"]["wkv_b"] == P(None, None, "tp", None)
    assert specs["layers"][1]["mla"]["wo"] == P(None, "tp", None, None)
    assert specs["layers"][1]["mla"]["wq_a"] == P(None, None, None)
    assert specs["layers"][1]["mla"]["kv_norm"] == P(None, None)
    assert specs["layers"][1]["moe"]["ws1"] == P(None, None, "tp")
    assert specs["layers"][1]["moe"]["w1"] == P(None, "ep", None, None)
    with pytest.raises(ValueError, match="layer pattern kind"):
        TransformerConfig(layer_pattern=(("window", "dense", 1),))
    with pytest.raises(ValueError, match="layer pattern run"):
        TransformerConfig(layer_pattern=(("mha", "sparse", 1),))
    with pytest.raises(ValueError, match="mla sizes"):
        TransformerConfig(layer_pattern=(("mla", "dense", 1),))
    # the next-token loss runs the pattern too (no module asked for)
    loss, counters = loss_and_counters(
        init_params(jax.random.PRNGKey(0), _cfg(mtp_depth=0)),
        {"tokens": jnp.asarray(_batches(3)[0])}, _cfg(mtp_depth=0))
    assert np.isfinite(float(loss)) and "moe_held_choices" in counters


def test_the_spans_and_counters_have_readers():
    """The new scopes own instructions in the manifest the program
    publishes of the step it ran (what the benchmark's scope readers
    read: ``tests/test_program_spans.py``); the new counters, where a
    worker reports them, are gauges on /metrics; the two
    latent-attention roofline readers find nothing in a trace without
    the kernels and a share where they are."""
    from benchmarks import run as bench_run
    from ray_tpu._private.metrics_agent import get_metrics_registry
    from ray_tpu.train.session import Session
    from ray_tpu.util import tracing
    cfg = _cfg()
    state, tx = make_train_state(jax.random.PRNGKey(1), cfg)
    step = make_train_step(cfg, tx, loss_override=functools.partial(
        mtp.loss_fn, cfg=cfg, coeff=0.3))
    tracing.clear()
    step(state, {"tokens": jnp.asarray(_batches(3)[0])})
    owners = {scope for scope, _ in
              tracing.programs()["train_step"]["scopes"].values()}
    tracing.clear()
    for scope in ("mla_q", "mla_kv", "mla_out", "moe_shared", "mtp_module",
                  "mtp_loss", "moe_router", "attention", "moe_bias"):
        assert scope in owners, scope

    session = Session(lambda: None, 2, 0, 4)
    session.report(loss=1.0, main_loss=0.8, mtp_loss=0.7,
                   moe_bias_abs_max=0.003, moe_load_cv=0.25)
    exposed = get_metrics_registry().render_prometheus().splitlines()
    for line in ('ray_tpu_train_main_loss{rank="2"} 0.8',
                 'ray_tpu_train_mtp_loss{rank="2"} 0.7',
                 'ray_tpu_train_moe_bias_abs_max{rank="2"} 0.003',
                 'ray_tpu_train_moe_load_cv{rank="2"} 0.25'):
        assert line in exposed, line

    ctx = {"trace": {"device_ops": {"/device:TPU:0": [
        ["fusion.1", 0.0, 5e6]]}, "host_spans": []},
        "facts": {"rows": 2, "seq_len": 8192}, "device_kind": "TPU v5 lite",
        "config": {"num_attention_heads": 32, "qk_nope_head_dim": 128,
                   "qk_rope_head_dim": 64, "v_head_dim": 128,
                   "kv_lora_rank": 512}}
    fwd = bench_run._reader("mla_flash_fwd_roofline")
    bwd = bench_run._reader("mla_flash_bwd_roofline")
    assert fwd(ctx) is None and bwd(ctx) is None
    # one forward call: 2 x 320 x 32 x 4,096 x 16,384 = 1.3744 TFLOP, 6.977
    # ms at 197 TFLOP/s; the backward twice that
    ctx["trace"]["device_ops"]["/device:TPU:0"] += [
        ["flash_attention_fwd.3", 1e7, 13.953e6],
        ["flash_attention_bwd.4", 1e8, 55.81e6]]
    assert fwd(ctx) == pytest.approx(50.0, rel=1e-3)
    assert bwd(ctx) == pytest.approx(25.0, rel=1e-3)
    assert fwd(dict(ctx, config={"hidden_size": 2048})) is None
