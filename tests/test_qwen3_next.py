"""The hybrid stack: Gated DeltaNet layers (``models/gdn.py`` over
``ops/gated_delta.py``), gated attention with partial rotary and
``1 + w`` norms, the gated shared expert (``models/moe.py``) and the
period scan (``models/transformer.py``), at a tiny size against the
benchmark's plain reference (``benchmarks/reference/gdn_gated_moe.py``),
whose delta rule runs token by token."""

import dataclasses
import hashlib
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from ray_tpu.models import moe  # noqa: E402
from ray_tpu.models.gdn import GDNConfig  # noqa: E402
from ray_tpu.models.transformer import (  # noqa: E402
    TransformerConfig, init_params, loss_and_counters, make_train_state,
    make_train_step, param_specs)

# Two periods of 2 delta layers + 1 attention layer: 2 key heads of 8
# serving 4 value heads of 8, chunks of 16; 4 query heads of 16 on 2 K/V
# heads, rotary on 4 columns; 16 experts of which this rank holds 4
# (experts 4-7), 4 a token, a gated shared expert.
CONFIG = {
    "reference": "gdn_gated_moe", "hidden_size": 64,
    "moe_intermediate_size": 32, "shared_expert_intermediate_size": 32,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "partial_rotary_factor": 0.25, "rope_theta": 10000000,
    "linear_num_key_heads": 2, "linear_num_value_heads": 4,
    "linear_key_head_dim": 8, "linear_value_head_dim": 8,
    "linear_conv_kernel_dim": 4, "gdn_chunk": 16,
    "full_attention_interval": 3, "num_hidden_layers": 6, "vocab_size": 128,
    "num_experts": 16, "num_experts_per_tok": 4, "num_experts_held": 4,
    "experts_held_first": 4, "norm_topk_prob": True,
    "router_aux_loss_coef": 0.001, "dispatch_alike_tail": 0.01,
    "rms_norm_eps": 1e-6, "initializer_range": 0.02, "dtype": "float32",
    "remat": True,
    "optimizer": {"name": "adamw", "learning_rate": 3e-4, "b1": 0.9,
                  "b2": 0.95, "eps": 1e-8, "weight_decay": 0.1},
}
TRAFFIC = {"kind": "packed_documents", "rows": 2, "seq_len": 32,
           "pool_batches": 2, "doc_len": {"alpha": 1.2, "min": 4, "max": 64},
           "bos_id": 0}
CELL = {"check": {"steps": 2}}
# float32 on both sides, the reference following the program's experts:
# summation order, and the chunked rule's inverse against the recurrence
# (1e-5 of a layer's output, tests/test_gated_delta.py).  The weakest
# control by these two (rotary on every column) reads 10 times them;
# a state, or its cotangent, kept in bfloat16 moves no norm by 1e-4 at 32
# positions and is told by the rule alone, forward and backward
# (``gdn_rule_gap``, ``gdn_rule_grad_gap``: the norm of a difference),
# where the program reads 1e-6 and those controls 1e-3 and more.
LIMITS = {"grad1_norm_gap": 1e-3, "change_norm_gap": 2e-3,
          "gdn_rule_gap": 1e-4, "gdn_rule_grad_gap": 1e-4}
LOSS_GAP = 1e-5


def _cfg(**changes):
    from benchmarks.drivers import trainer_gdn_steps as driver
    kwargs = driver._model_kwargs(CONFIG, TRAFFIC["seq_len"])
    kwargs["gdn"] = GDNConfig(**kwargs["gdn"])
    return TransformerConfig(dtype=jnp.float32, **dict(kwargs, **changes))


def _batches(seed):
    from benchmarks.harness import traffic
    return list(traffic.generate(TRAFFIC, seed, vocab_size=128))


def _program(seed, batches):
    """Two steps of ``make_train_step`` from the seed's weights -> what
    the reference returns."""
    from benchmarks.drivers import trainer_gdn_steps as driver
    from benchmarks.drivers.trainer_steps import _adam_mu
    from benchmarks.harness import gdn_weights
    cfg = _cfg()
    state, tx = make_train_state(
        jax.random.PRNGKey(0), cfg,
        learning_rate=CONFIG["optimizer"]["learning_rate"])
    start = gdn_weights.make_hybrid(seed, CONFIG, jnp.float32)
    assert jax.tree.map(jnp.shape, start) == jax.tree.map(
        jnp.shape, state["params"])
    # the program's own draw starts its norms and gates where the
    # benchmark's does
    for mine, theirs in ((state["params"]["ln_f"], start["ln_f"]),
                         (state["params"]["layers"][0][0]["gdn"]["norm"],
                          start["layers"][0][0]["gdn"]["norm"]),
                         (state["params"]["layers"][0][1]["q_norm"],
                          start["layers"][0][1]["q_norm"])):
        assert np.array_equal(mine, theirs)
    state["params"] = start
    step = make_train_step(cfg, tx)
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
    again = gdn_weights.make_hybrid(seed, CONFIG, jnp.float32)
    out["change_norm"] = {k: np.asarray(v, np.float64) for k, v in
                          driver.leaf_norms(jax.tree.map(
                              lambda a, b: a - b, state["params"],
                              again)).items()}
    # the rule alone and its vjp, both kernels interpreted, on the seed's
    # probe at the step's shape
    out["rule_probe"] = driver.rule_probe(
        CONFIG, seed, TRAFFIC["rows"], TRAFFIC["seq_len"], use_pallas=True,
        interpret=True)
    return out


def _numbers(prog, ref):
    from benchmarks.harness import compare
    from benchmarks.reference import gdn_gated_moe as reference
    return dict(compare.train_numbers(prog, ref),
                **reference.rule_gaps(prog["rule_probe"], ref["rule_probe"]))


def test_program_matches_the_plain_reference_and_the_controls_do_not():
    """The whole loss, the first gradient leaf by leaf and the
    parameters' change over two AdamW steps."""
    from benchmarks.drivers import trainer_gdn_steps as driver
    from benchmarks.harness import compare
    seed = 2**31 + 9
    batches = _batches(seed)
    prog = _program(seed, batches)
    ref = driver.follow_reference(CELL, CONFIG, seed, batches,
                                  choices=prog["choices"])
    # 6 layers, 2 rows x 32 positions, 4 choices, in the layers' order
    assert prog["choices"][0].shape == (6, 2, 32, 4)
    assert ref["routing_gap"][0] <= 1e-6, ref["routing_gap"]
    assert max(compare.loss_gaps(prog, ref)) <= LOSS_GAP
    correct, compared = compare.judge(_numbers(prog, ref), LIMITS)
    assert correct, compared
    # every leaf of both kinds of layer is among the compared, a norm a
    # layer: 2 periods x 2 delta layers, 2 x 1 attention layers
    leaves = set(prog["grad1_norm"])
    assert {"layers.0.0.gdn.w_qkvz", "layers.0.0.gdn.conv",
            "layers.0.0.gdn.A_log", "layers.0.0.gdn.dt_bias",
            "layers.0.0.gdn.norm", "layers.0.0.moe.wsg", "layers.0.1.wq",
            "layers.0.1.q_norm", "layers.0.1.moe.wr", "embed",
            "lm_head"} <= leaves
    assert prog["grad1_norm"]["layers.0.0.gdn.wo"].shape == (4,)
    assert prog["grad1_norm"]["layers.0.1.wk"].shape == (2,)
    for metrics, (main, aux) in zip(prog["metrics"], ref["loss_parts"]):
        assert metrics["moe_dropped_choices"] == 0.0
        assert 0 < metrics["moe_held_choices"] < 2 * 32 * 4
        # the auxiliary is in the loss, at its coefficient
        assert metrics["loss"] == pytest.approx(main + aux, rel=1e-5)
        assert aux == pytest.approx(0.001 * metrics["moe_balance_loss"],
                                    rel=1e-4)
        assert 0.4 < metrics["attn_gate_mean"] < 0.6
        assert 0.4 < metrics["moe_shared_gate_mean"] < 0.6
        assert 0.4 < metrics["gdn_beta_mean"] < 0.6
        assert 0 < metrics["gdn_decay_mean"] < 1
        assert metrics["gdn_state_norm"] > 0
    # each control in the program's place, its experts followed likewise
    for how in (dict(precision="fp8"), dict(decay=False),
                dict(state="bfloat16"), dict(dstate="bfloat16"),
                dict(attn_gate=False), dict(rotary="all"),
                dict(shared_gate=False)):
        control = driver.follow_reference(CELL, CONFIG, seed, batches, **how)
        ref = driver.follow_reference(CELL, CONFIG, seed, batches,
                                      choices=control["choices"])
        correct, compared = compare.judge(_numbers(control, ref), LIMITS)
        assert not correct, (how, compared)
        if "state" in how or "decay" in how:
            assert compared["gdn_rule_gap"]["value"] > 1e-3, compared
        if "dstate" in how:
            # the forward is the sound one's: the gradients alone tell
            assert compared["gdn_rule_gap"]["value"] == 0.0, compared
            assert compared["gdn_rule_grad_gap"]["value"] > 1e-3, compared


def test_the_shares_add_up_to_the_uncut_layer():
    """The sixteen shares of one expert each, the gated shared expert
    counted once, sum to what the plain reference gives for the whole
    expert layer with all sixteen experts."""
    from benchmarks.reference import gdn_gated_moe as reference
    cfg = _cfg(moe_experts_held=None)
    lp = jax.tree.map(lambda a: a[0, 0], init_params(
        jax.random.PRNGKey(7), cfg)["layers"][0][0]["moe"])
    # a router that spreads, a gate that is not one half everywhere
    lp["wr"] = lp["wr"] * 40.0
    lp["wsg"] = lp["wsg"] * 40.0
    h = jax.random.normal(jax.random.PRNGKey(8), (2, 32, 64), jnp.float32)
    gate = moe.shared_gate(h, lp)
    assert float(jnp.std(gate)) > 0.05
    total = moe.shared_expert(h, lp) * gate
    seen = []
    for first in range(16):
        share = dict(lp, **{k: lp[k][first:first + 1]
                            for k in ("w1", "w3", "w2")})
        y, stats = moe.moe_ffn(h, share, 4, True, held=(first, 1))
        assert int(stats["dropped_choices"]) == 0
        total = total + y
        seen.append(int(stats["held_choices"]))
    # every choice lands on exactly one share
    assert sum(seen) == 2 * 32 * 4
    hp = {"top_k": 4, "norm_topk": True, "first": 0, "shared_gate": True,
          "aux_scale": 0.0}
    flat = {"moe." + k: v for k, v in lp.items()}
    for r in range(2):
        want, _, _ = reference._experts(flat, h[r], hp, "float32", None,
                                        jnp.zeros((16,)))
        assert float(jnp.max(jnp.abs(total[r] - want))) <= 2e-5
        # and the gated shared expert alone is not nothing
        assert float(jnp.max(jnp.abs(
            (moe.shared_expert(h, lp) * gate)[r]))) > 1e-3


def _written_out(params, cfg):
    """A period pattern's tree and configuration as runs written out:
    every repeat's runs one after another."""
    (runs, repeats), = cfg.layer_pattern
    (stacks,) = params["layers"]
    flat_pattern = tuple(run for _ in range(repeats) for run in runs)
    flat_layers = tuple(jax.tree.map(lambda a, p=p: a[p], stack)
                        for p in range(repeats) for stack in stacks)
    return (dict(params, layers=flat_layers),
            dataclasses.replace(cfg, layer_pattern=flat_pattern))


def test_the_period_scan_at_count_three_is_three_periods_written_out():
    """One scan over three periods of (2 delta layers, 1 attention
    layer) against the same nine layers as six runs: the loss, every
    counter and every gradient leaf."""
    cfg = _cfg(layer_pattern=(((("gdn", "moe", 2), ("mha", "moe", 1)), 3),))
    assert cfg.n_layers == 9 and cfg.moe_layers == 9
    params = init_params(jax.random.PRNGKey(3), cfg)
    batch = {"tokens": jnp.asarray(_batches(5)[0])}
    flat_params, flat_cfg = _written_out(params, cfg)
    assert len(flat_cfg.layer_pattern) == 6 and flat_cfg.n_layers == 9

    def run(p, c):
        (loss, counters), grads = jax.value_and_grad(
            lambda p: loss_and_counters(p, batch, c), has_aux=True)(p)
        return loss, counters, grads

    loss, counters, grads = jax.jit(lambda p: run(p, cfg))(params)
    want_loss, want_counters, want_grads = jax.jit(
        lambda p: run(p, flat_cfg))(flat_params)
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-6)
    assert sorted(counters) == sorted(want_counters)
    assert counters["moe_choices"].shape == (9, 2, 32, 4)
    for name in counters:
        np.testing.assert_allclose(counters[name], want_counters[name],
                                   rtol=1e-5, atol=1e-7, err_msg=name)
    got, _ = _written_out(grads, cfg)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                            jax.tree.leaves(want_grads)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-7,
                                   err_msg=str(path))


def test_the_pattern_says_periods_and_the_kinds_need_their_sizes():
    cfg = _cfg()
    assert cfg.layer_pattern == (
        ((("gdn", "moe", 2), ("mha", "moe", 1)), 2),)
    assert cfg.n_layers == 6 and cfg.moe_layers == 6
    params = init_params(jax.random.PRNGKey(0), cfg)
    (stacks,) = params["layers"]
    assert stacks[0]["gdn"]["w_qkvz"].shape == (2, 2, 64, 2, 48)
    assert stacks[1]["wq"].shape == (2, 1, 64, 4, 32)       # query | gate
    assert stacks[0]["moe"]["wsg"].shape == (2, 2, 64, 1)
    # the (1 + w) norms start at nought, the delta layer's own at one
    assert float(jnp.max(jnp.abs(stacks[0]["ln1"]))) == 0.0
    assert float(jnp.min(stacks[0]["gdn"]["norm"])) == 1.0
    assert jax.tree.structure(jax.tree.map(lambda a: 0, params)) == \
        jax.tree.structure(jax.tree.map(
            lambda s: 0, param_specs(cfg),
            is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec)))
    # a spec a leaf, the periods one more unsharded leading axis
    specs = param_specs(cfg)["layers"][0][0]
    assert specs["gdn"]["w_qkvz"] == jax.sharding.PartitionSpec(
        None, None, None, "tp", None)
    assert len(specs["gdn"]["w_qkvz"]) == stacks[0]["gdn"]["w_qkvz"].ndim
    with pytest.raises(ValueError, match="gdn sizes"):
        TransformerConfig(layer_pattern=(("gdn", "dense", 1),))
    with pytest.raises(ValueError, match="period"):
        TransformerConfig(layer_pattern=(((), 2),))
    with pytest.raises(ValueError, match="multi-token"):
        _cfg(mtp_depth=1)
    # a run beside a period, and the defaults' single run as it was
    mixed = _cfg(layer_pattern=(("mha", "dense", 1),
                                ((("gdn", "moe", 1),), 2)))
    assert mixed.n_layers == 3 and mixed.moe_layers == 2
    loss, counters = loss_and_counters(
        init_params(jax.random.PRNGKey(0), mixed),
        {"tokens": jnp.asarray(_batches(3)[0])}, mixed)
    assert np.isfinite(float(loss))
    assert "attn_gate_mean" in counters and "gdn_beta_mean" in counters
    assert TransformerConfig().layer_pattern == (("mha", "dense", 4),)


def test_the_delta_layer_hands_the_rule_its_key_heads(monkeypatch):
    """``gdn_attention`` calls the rule with q and k at their key heads
    (2 here, for 4 value heads) and gets what the parent got by writing
    each key head out once a value head that reads it: values, counters
    and the gradient of every ``gdn`` leaf (``jnp.repeat``'s transpose
    summed a key head's value heads; the rule now does)."""
    from ray_tpu.models import gdn
    from ray_tpu.ops import gated_delta
    cfg = _cfg()
    lp = jax.tree.map(lambda x: x[0], gdn.init_gdn_params(
        jax.random.PRNGKey(3), 1, cfg.d_model, cfg.gdn, jnp.float32))
    h, dout = jax.random.normal(jax.random.PRNGKey(4), (2, 2, 32, cfg.d_model))
    heads, rule = [], gated_delta.gated_delta_rule

    def run(lp):
        out, counted = gdn.gdn_attention(h, lp, cfg)
        return jnp.sum(out * dout), (out, counted)

    def repeated(q, k, v, *rest, **how):
        heads.append(q.shape[2])
        r = v.shape[2] // q.shape[2]
        return rule(jnp.repeat(q, r, axis=2), jnp.repeat(k, r, axis=2), v,
                    *rest, **how)

    (_, (out, counted)), grads = jax.value_and_grad(run, has_aux=True)(lp)
    monkeypatch.setattr(gated_delta, "gated_delta_rule", repeated)
    (_, (want, counted_), ), want_grads = jax.value_and_grad(
        run, has_aux=True)(lp)
    assert heads == [cfg.gdn.num_key_heads] and cfg.gdn.ratio == 2
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-7)
    for name in counted:
        np.testing.assert_allclose(counted[name], counted_[name], rtol=1e-5)
    assert set(grads) == set(lp)
    for name in lp:
        scale = float(jnp.max(jnp.abs(want_grads[name])))
        assert scale > 0, name
        np.testing.assert_allclose(grads[name] / scale,
                                   want_grads[name] / scale, atol=1e-5,
                                   err_msg=name)


def test_the_scopes_and_counters_have_readers():
    """The new scopes own instructions in the manifest the program
    publishes of the step it ran (what the benchmark's scope readers
    read: ``tests/test_program_spans.py``); the new counters, where a
    worker reports them, are gauges on /metrics."""
    from ray_tpu._private.metrics_agent import get_metrics_registry
    from ray_tpu.train.session import Session
    from ray_tpu.util import tracing
    cfg = _cfg()
    state, tx = make_train_state(jax.random.PRNGKey(1), cfg)
    step = make_train_step(cfg, tx)
    tracing.clear()
    step(state, {"tokens": jnp.asarray(_batches(3)[0])})
    owners = {scope for scope, _ in
              tracing.programs()["train_step"]["scopes"].values()}
    tracing.clear()
    for scope in ("gdn_proj", "gdn_conv", "gdn_core", "gdn_out", "attn_gate",
                  "moe_shared", "moe_router", "attention", "ffn"):
        assert scope in owners, scope

    session = Session(lambda: None, 3, 0, 4)
    session.report(loss=1.0, gdn_state_norm=0.02, gdn_decay_mean=0.05,
                   gdn_beta_mean=0.5, attn_gate_mean=0.49,
                   moe_shared_gate_mean=0.51)
    exposed = get_metrics_registry().render_prometheus().splitlines()
    for line in ('ray_tpu_train_gdn_state_norm{rank="3"} 0.02',
                 'ray_tpu_train_gdn_decay_mean{rank="3"} 0.05',
                 'ray_tpu_train_gdn_beta_mean{rank="3"} 0.5',
                 'ray_tpu_train_attn_gate_mean{rank="3"} 0.49',
                 'ray_tpu_train_moe_shared_gate_mean{rank="3"} 0.51'):
        assert line in exposed, line


def step_jaxpr_hash(cell: str, root: str = ROOT) -> str:
    """sha256 of the text of the jaxpr of a benchmark cell's train step
    at the cell's own sizes, traced (nothing is compiled) as on a TPU so
    that both flash kernels are in it.  PARENT_STEPS below was made by
    this very function with the parent commit's tree first on
    ``sys.path``."""
    import functools
    from ray_tpu.models.transformer import (TransformerConfig,
                                            make_train_state, make_train_step)
    def load(kind, name):
        with open(f"{root}/benchmarks/{kind}/{name}.json") as f:
            return json.load(f)

    wl = load("workloads", cell)
    config = load("configs", wl["config"])
    traffic = load("traffic", wl["traffic"])
    driver = __import__(f"benchmarks.drivers.{wl['driver']}",
                        fromlist=["_model_kwargs"])
    kwargs = driver._model_kwargs(config, traffic["seq_len"])
    rows, length = traffic["rows"], traffic["seq_len"]
    batch, over = {"tokens": ((rows, length + 1), jnp.int32)}, None
    if "mla" in kwargs:
        from ray_tpu.models.mla import MLAConfig
        kwargs["mla"] = MLAConfig(**kwargs["mla"])
    if "gdn" in kwargs:
        kwargs["gdn"] = GDNConfig(**kwargs["gdn"])
    if "mamba" in kwargs:
        from ray_tpu.models.mamba import MambaConfig
        kwargs["mamba"] = MambaConfig(**kwargs["mamba"])
    if "mamba2" in kwargs:
        from ray_tpu.models.mamba2 import Mamba2Config
        kwargs["mamba2"] = Mamba2Config(**kwargs["mamba2"])
    if "rope_tables" in kwargs:
        from ray_tpu.models.transformer import RopeTable
        kwargs["rope_tables"] = {name: RopeTable(**table) for name, table
                                 in kwargs["rope_tables"].items()}
    cfg = TransformerConfig(dtype=jnp.dtype(config["dtype"]), **kwargs)
    if wl["driver"] == "trainer_blockdiff_steps":
        from ray_tpu.models import block_diffusion
        over = functools.partial(
            block_diffusion.loss_fn, cfg=cfg,
            block=config["block_diffusion"]["block_length"])
        batch = {"tokens": ((rows, length), jnp.int32),
                 "noisy": ((rows, length), jnp.int32),
                 "weight": ((rows, length), jnp.float32)}
    elif wl["driver"] == "trainer_mla_mtp_steps":
        from ray_tpu.models import mtp
        over = functools.partial(mtp.loss_fn, cfg=cfg,
                                 coeff=config["mtp_loss_coef"])
    box = []

    def build(key):
        state, tx = make_train_state(key, cfg)
        box.append(tx)
        return state

    state = jax.eval_shape(build, jax.random.PRNGKey(0))
    step = make_train_step(cfg, box[0], loss_override=over)
    backend = jax.default_backend
    jax.default_backend = lambda: "tpu"
    try:
        text = str(step.trace(state, {
            k: jax.ShapeDtypeStruct(*v) for k, v in batch.items()}).jaxpr)
    finally:
        jax.default_backend = backend
    return hashlib.sha256(
        re.sub(r"0x[0-9a-f]+", "0x", text).encode()).hexdigest()


# The dense cell's step at the commit 50ae53a (PR 34), which it still
# equals, and the state-space cell's at 7f1202d (PR 40: its convolution
# still calls the plain ``causal_conv``).  Since
# PR 39 the layers' cut points carry names (``checkpoint_name``: metadata
# that lowers to nothing), which are equations of the jaxpr: the test
# below takes the names out and finds these hashes, so the names are all
# that differs where no device reports a limit.  The four sparse cells'
# steps are PR 43's own, made by ``step_jaxpr_hash`` on PR 43's tree with
# the names taken out as the test does: the expert layer's backward makes
# eight grouped products a chunk for nine (``models/moe.py``), so every
# step that runs ``_held_experts_bwd`` differs from its parent's
# (f137e57d..., 7acc7897..., e839d08e... -- PR 41's own, its convolution
# the kernel pair -- and 5fe7ef97... at PR 42).  The two cells that run no
# line of ``moe.py`` keep the hashes they had: that they still pass says
# those two steps are the parent's.  The hybrid cell's is PR 44's own, made
# the same way (3fc5faa1... at PR 43): the delta rule's forward kernel is
# one kernel that also writes the state entering each grid step, its
# backward walks a step's states itself, and the rule's two names stay in
# as the flash kernel's do; the five cells without a delta layer keep
# PR 43's hashes.
PARENT_STEPS = {
    "train-dscoder-1b3.pack4k":
        "593226c3e798e87962790db2f4055938f8862739301114386319090f98a5021b",
    "train-sdar-30b-a3b.blockdiff4k":
        "94e7170b4118a422fbb3bc1f7e0e180ccbf94a731de725dedf91c6ebfddb3e28",
    "train-joyai-flash.pack8k":
        "6923b2157b98658fa86976e72d246b878723b0aecb85cc49a5b8ea97a28833a3",
    "train-qwen3-next.pack8k":
        "279ed63714177eae4ded5732ad3a5db2c6b44799f7434a36f8f7b719fa08bd1f",
    "train-phi4-mini-flash.pack16k":
        "ef676578e0020595e3ae2f5d9b63594d720dd9670c02ef45bb2f7c366b65b715",
    "train-laguna-s.pack16k":
        "610498e3e64b55cef825b379a27ea789e9e7062a46139dd7907e4ede4144784b",
    # the step since the Mamba-2 rule's kernels read x, B, C and dt
    # positions-minor and write y so (0f387e11... when the cell was
    # added); the six above are as they were
    "train-nemotron-3-super.row8k":
        "8dad20e2fa3130a203fdb6adf9469c8ee12f041ae84ae4be6d4942daecbde76c",
}


@pytest.mark.parametrize("cell", sorted(PARENT_STEPS))
def test_the_older_cells_steps_are_traced_as_the_parent_traced_them(
        cell, monkeypatch):
    """What gated attention, the ``1 + w`` norms, the period scan, the
    shared expert's gate and the forward kernel's VMEM rule added is
    behind defaults that leave the dense, block-diffusion and
    latent-attention steps' jaxprs equal to the parent's, both flash
    kernels and their compiler parameters included.  Since PR 37 the
    hybrid cell's too: the registry of compiled programs touches
    nothing inside ``jax.jit``.  Since PR 39, with the names of the
    layers' cut points taken out (``models/remat.py``'s candidates; the
    flash kernel's two stay): where no device reports a memory limit --
    here -- nothing is planned, every ``remat_layer`` has the parent's
    policy and the step is differentiated as the parent's was, so the
    names are the whole difference, and they lower to nothing.  Since
    PR 43 the four sparse cells' hashes are that PR's steps (the expert
    layer's backward changed); the dense and the state-space cell's are
    the ones they had.  Since PR 44 the hybrid cell's is that PR's (the
    delta rule's kernels changed; nothing else did: the other five
    pass as they stood).  PR 46 added its cell's step and left the six
    as they were: the squared-ReLU and latent experts, the convolution's
    bias and the layer that is a mixer alone are behind defaults."""
    import importlib
    from ray_tpu.models import common, gdn, mha, mla, moe, transformer
    for module in (common, gdn, mha, mla, moe, transformer):
        monkeypatch.setattr(module, "checkpoint_name", lambda x, name: x)
    monkeypatch.setattr(importlib.import_module(
        "ray_tpu.ops.flash_attention"), "_kept_out", lambda out: out)
    assert step_jaxpr_hash(cell) == PARENT_STEPS[cell]

