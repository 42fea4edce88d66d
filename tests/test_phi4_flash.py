"""The decoder-hybrid-decoder slice: Mamba layers (``models/mamba.py``
over ``ops/selective_scan.py``), differential attention under a window,
over everything before and across (``models/diff_attention.py``), the
Gated Memory Unit, the shared slot, LayerNorms with a bias and the tied
head (``models/transformer.py``), at a tiny size against the benchmark's
plain reference (``benchmarks/reference/sambay_decoder.py``), whose
recurrence runs token by token."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from ray_tpu.models.mamba import MambaConfig  # noqa: E402
from ray_tpu.models.transformer import (  # noqa: E402
    TransformerConfig, forward, init_params, loss_and_counters, loss_fn,
    make_train_state, make_train_step, param_specs, run_options)

# Layers 14-19 of 32: Mamba, window of 8, Mamba that hands on its scan
# output, full attention that hands on its keys and values, a Gated
# Memory Unit, cross attention; 8 query heads of 8 on 4 K/V heads (4
# pairs on 2), 128 channels of 4 states in chunks of 8.
CONFIG = {
    "reference": "sambay_decoder", "hidden_size": 64,
    "intermediate_size": 96, "num_attention_heads": 8,
    "num_key_value_heads": 4, "layer_norm_eps": 1e-5, "mb_per_layer": 2,
    "num_hidden_layers": 6, "sliding_window": 8,
    "tie_word_embeddings": True, "vocab_size": 128,
    "layer_indices": [14, 15, 16, 17, 18, 19],
    "mamba_d_inner": 128, "mamba_d_state": 4, "mamba_d_conv": 4,
    "mamba_dt_rank": 4, "mamba_dt_min": 0.001, "mamba_dt_max": 0.1,
    "lambda_std": 0.1, "ssm_chunk": 8, "initializer_range": 0.02,
    "dtype": "float32", "remat": True,
    "published": {"num_hidden_layers": 32, "vocab_size": 1024},
    "optimizer": {"name": "adamw", "learning_rate": 3e-4, "b1": 0.9,
                  "b2": 0.95, "eps": 1e-8, "weight_decay": 0.1},
}
TRAFFIC = {"kind": "packed_documents", "rows": 2, "seq_len": 32,
           "pool_batches": 2, "doc_len": {"alpha": 1.2, "min": 4, "max": 64},
           "bos_id": 0}
CELL = {"check": {"steps": 2}}
# float32 on both sides: summation order alone (the chunked scan is the
# recurrence; attention's softmax is whole against blockwise).  Read:
# grad1_norm_gap 3.0e-6, change_norm_gap 3.9e-6, the rule's two 0 and
# 5.5e-7; the weakest control by the first two (the unit gating another
# Mamba layer's output) reads 0.19 and 0.07, a state kept in bfloat16
# moves no norm and is told by the rule alone (0.0087, 0.024).
LIMITS = {"grad1_norm_gap": 1e-3, "change_norm_gap": 2e-3,
          "ssm_rule_gap": 1e-4, "ssm_rule_grad_gap": 1e-4}
LOSS_GAP = 1e-5


def _cfg(**changes):
    from benchmarks.drivers import trainer_sambay_steps as driver
    kwargs = driver._model_kwargs(CONFIG, TRAFFIC["seq_len"])
    kwargs["mamba"] = MambaConfig(**kwargs["mamba"])
    return TransformerConfig(dtype=jnp.float32, **dict(kwargs, **changes))


def _batches(seed):
    from benchmarks.harness import traffic
    return list(traffic.generate(TRAFFIC, seed, vocab_size=128))


def _weights(seed):
    from benchmarks.harness import sambay_weights
    return sambay_weights.make_sambay(seed, CONFIG, jnp.float32)


def _program(seed, batches):
    """Two steps of ``make_train_step`` from the seed's weights -> what
    the reference returns."""
    from benchmarks.drivers import trainer_sambay_steps as driver
    from benchmarks.drivers.trainer_steps import _adam_mu
    cfg = _cfg()
    state, tx = make_train_state(
        jax.random.PRNGKey(0), cfg,
        learning_rate=CONFIG["optimizer"]["learning_rate"])
    start = _weights(seed)
    assert jax.tree.map(lambda a: (a.shape, a.dtype), start) == jax.tree.map(
        lambda a: (a.shape, a.dtype), state["params"])
    # the program's own draw starts what is no N(0, std) matrix where the
    # benchmark's does
    mine, theirs = state["params"]["layers"], start["layers"]
    for a, b in ((mine[0]["mamba"]["A_log"], theirs[0]["mamba"]["A_log"]),
                 (mine[0]["mamba"]["D"], theirs[0]["mamba"]["D"]),
                 (mine[1]["diff"]["subln"], theirs[1]["diff"]["subln"]),
                 (mine[1]["ln1_b"], theirs[1]["ln1_b"]),
                 (state["params"]["ln_f"], start["ln_f"])):
        assert np.array_equal(a, b)
    state["params"] = start
    step = make_train_step(cfg, tx)
    out = {"losses": [], "metrics": []}
    for i, batch in enumerate(batches):
        state, metrics = step(state, {"tokens": jnp.asarray(batch)})
        out["losses"].append(float(metrics["loss"]))
        out["metrics"].append({k: float(v) for k, v in metrics.items()})
        if i == 0:
            out["grad1_norm"] = {
                k: np.asarray(v, np.float64) / (1.0 - 0.9) for k, v in
                driver.leaf_norms(_adam_mu(state["opt"])).items()}
    out["change_norm"] = {k: np.asarray(v, np.float64) for k, v in
                          driver.leaf_norms(jax.tree.map(
                              lambda a, b: a - b, state["params"],
                              _weights(seed))).items()}
    # the scan alone and its vjp, both kernels interpreted: 128 channels
    # are no whole block, so the probe is the cell's 1,024-channel one
    probe_cfg = dict(CONFIG, mamba_d_inner=1024)
    out["rule_probe"] = driver.rule_probe(
        probe_cfg, seed, TRAFFIC["rows"], TRAFFIC["seq_len"],
        use_pallas=True, interpret=True)
    return out


@pytest.fixture(scope="module")
def followed():
    from benchmarks.drivers import trainer_sambay_steps as driver
    seed = 2 ** 31 + 5
    batches = _batches(seed)
    program = _program(seed, batches)
    reference = driver.follow_reference(CELL, CONFIG, seed, batches)
    probe = driver._reference(CONFIG)
    reference["rule_probe"] = probe.rule_probe(probe.rule_probe_inputs(
        seed, dict(CONFIG, mamba_d_inner=1024), TRAFFIC["rows"],
        TRAFFIC["seq_len"]))
    return program, reference, batches, seed


def test_two_steps_follow_the_plain_reference(followed):
    """Loss, every leaf's first gradient and every leaf's change over
    two AdamW steps, and the scan alone with its five gradients, within
    ``LIMITS`` (their reasons are beside them)."""
    from benchmarks.harness import compare
    from benchmarks.reference import sambay_decoder
    program, reference, _, _ = followed
    assert sorted(program["grad1_norm"]) == sorted(reference["grad1_norm"])
    for mine, theirs in zip(program["losses"], reference["losses"]):
        assert abs(mine - theirs) <= LOSS_GAP * abs(theirs)
    numbers = compare.train_numbers(program, reference)
    numbers.update(sambay_decoder.rule_gaps(program["rule_probe"],
                                            reference["rule_probe"]))
    correct, compared = compare.judge(numbers, LIMITS)
    assert correct, compared


def test_logits_follow_the_reference_and_the_head_is_the_embedding(followed):
    """``forward``'s logits against the reference's layers and head on
    the seed's weights, row by row; the tree has no ``lm_head``."""
    from benchmarks.harness import sambay_weights
    from benchmarks.reference import sambay_decoder as ref
    _, _, batches, seed = followed
    cfg, params = _cfg(), _weights(seed)
    assert "lm_head" not in params and "lm_head" not in param_specs(cfg)
    tokens = jnp.asarray(batches[0])[:, :-1]
    logits = forward(params, tokens, cfg)
    groups, _, order = ref._groups(params)
    plan = sambay_weights.layer_plan(CONFIG)
    hp = {"eps": 1e-5, "d_inner": 128, "d_state": 4, "dt_rank": 4,
          "state": "float32", "lam": "learned", "subln": True,
          "cross_kv": "handed", "kv_cotangent": True}
    for r in range(tokens.shape[0]):
        x, outs = groups["embed"]["embed"][tokens[r]], {}
        for entry, name in zip(plan, order):
            x, out = ref.layer(groups[name], x, outs.get(entry["reads"]),
                               entry, hp, "float32")
            if entry["writes"]:
                outs[entry["writes"]] = out
        want = ref._layer_norm(x, groups["final"]["ln_f"],
                               groups["final"]["ln_f_b"], 1e-5) \
            @ groups["embed"]["embed"].T
        np.testing.assert_allclose(logits[r], want, atol=2e-5)


def test_the_tied_heads_gradient_is_the_sum_of_both_uses():
    """d loss / d embed = (as the head, the lookup's output held fixed)
    + (as the lookup, the head's matrix held fixed)."""
    cfg, params = _cfg(), _weights(7)
    batch = {"tokens": jnp.asarray(_batches(7)[0])}
    whole = jax.grad(loss_fn)(params, batch, cfg)["embed"]

    def split(lookup, head):
        from ray_tpu.models import transformer as t
        tokens = batch["tokens"]
        x, _ = t.run_stacks(jnp.take(lookup, tokens[:, :-1], axis=0),
                            params["layers"], None, cfg)
        logits = jnp.einsum("bsd,vd->bsv", t.model_norm(x, params, "ln_f",
                                                        cfg), head)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, tokens[:, 1:, None], -1)[..., 0]
        return jnp.mean(logz - gold)

    as_lookup, as_head = jax.grad(split, (0, 1))(params["embed"],
                                                 params["embed"])
    assert float(jnp.linalg.norm(as_lookup)) > 0 < float(
        jnp.linalg.norm(as_head))
    np.testing.assert_allclose(whole, as_lookup + as_head, atol=1e-6)


def test_the_step_counts_the_scans_path_and_lambda_moves(followed):
    program, _, _, _ = followed
    first, second = program["metrics"]
    # off the TPU the scan is the jnp one, and the step says so
    assert first["ssm_scan_fallback_passes"] == 1.0
    assert 0.001 < first["ssm_delta_mean"] < 0.1
    # the mean over layers 15, 17, 19 of lambda: at lambda_init but for
    # the two exponentials' difference, and moving with the step
    inits = [0.8 - 0.6 * np.exp(-0.3 * i) for i in (15, 17, 19)]
    assert abs(first["diff_lambda"] - np.mean(inits)) < 0.05
    assert first["diff_lambda"] != second["diff_lambda"]


def test_what_a_layer_hands_on_is_read_by_the_layers_after_it():
    """The unit's output depends on layer 16's scan output and not on
    layer 14's; the cross layer's on layer 17's keys and values: by the
    gradients of the loss with one writer's path cut."""
    cfg, params = _cfg(remat=False), _weights(3)
    batch = {"tokens": jnp.asarray(_batches(3)[0])}
    grads = jax.grad(loss_fn)(params, batch, cfg)["layers"]
    # cut the readers: with the unit's and the cross layer's output
    # matrices noughted nothing reads the slot, and the writers'
    # gradients change (the readers' cotangents were summed into them)
    cut = jax.tree.map(lambda a: a, params)
    cut["layers"] = tuple(
        dict(stack, **{kind: dict(stack[kind], **{leaf: jnp.zeros_like(
            stack[kind][leaf])})}) if i == at else stack
        for i, stack in enumerate(cut["layers"])
        for at, kind, leaf in [(4, "gmu", "w_out") if i == 4 else
                               (5, "diff", "wo") if i == 5 else
                               (-1, "", "")])
    without = jax.grad(loss_fn)(cut, batch, cfg)["layers"]

    def moved(i, *path):
        a, b = grads[i], without[i]
        for key in path:
            a, b = a[key], b[key]
        return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(a))

    assert moved(2, "mamba", "w_x") > 1e-3          # M's cotangent
    assert moved(3, "diff", "wv") > 1e-3            # V's cotangent
    assert moved(3, "diff", "wk") > 1e-3            # K's cotangent


def test_the_pattern_says_what_each_run_reads_and_writes():
    from benchmarks.drivers import trainer_sambay_steps as driver
    assert driver.layer_pattern(CONFIG) == (
        ("mamba", "dense", 1), ("diff:window=8", "dense", 1),
        ("mamba:writes=memory", "dense", 1), ("diff:writes=kv", "dense", 1),
        ("gmu", "dense", 1), ("diff:reads=kv", "dense", 1))
    assert run_options("diff:window=512,writes=kv") == (
        "diff", {"window": 512, "writes": "kv"})
    assert run_options("gmu") == ("gmu", {"reads": "memory"})
    assert run_options("mha") == ("mha", {})
    cfg = _cfg()
    assert cfg.n_layers == 6 and cfg.first_layer_index == 14
    # the cross layer projects no keys or values
    params = init_params(jax.random.PRNGKey(0), cfg)
    assert "wk" in params["layers"][3]["diff"]
    assert "wk" not in params["layers"][5]["diff"]


@pytest.mark.parametrize("pattern,match", [
    ((("gmu", "dense", 1), ("mamba:writes=memory", "dense", 1)),
     "no earlier run writes"),
    ((("diff:reads=kv", "dense", 1),), "no earlier run writes"),
    ((("mamba:writes=memory", "dense", 1), ("diff:reads=kv", "dense", 1)),
     "no earlier run writes"),
    ((("mamba:writes=kv", "dense", 1),), "does not take"),
    ((("diff:window", "dense", 1),), "invalid literal|does not take"),
    ((("mha:writes=kv", "dense", 1),), "does not take"),
    ((("diff:reads=kv,writes=kv", "dense", 1),), "reads the slot it writes"),
    ((((("mamba:writes=memory", "dense", 1), ("gmu", "dense", 1)), 2),),
     "in a period"),
    ((("window", "dense", 1),), "layer pattern kind"),
])
def test_a_pattern_that_cannot_run_is_refused_when_it_is_made(pattern, match):
    """A reader before its writer, an option its kind does not take, the
    slot inside a period's scan."""
    with pytest.raises(ValueError, match=match):
        dataclasses.replace(_cfg(), layer_pattern=pattern)


def test_the_kinds_need_their_sizes_and_the_old_modules_keep_theirs():
    with pytest.raises(ValueError, match="mamba sizes"):
        TransformerConfig(layer_pattern=(("mamba", "dense", 1),))
    with pytest.raises(ValueError, match="own head"):
        TransformerConfig(tie_embeddings=True, mtp_depth=1,
                          layer_pattern=(("mha", "dense", 2),))
    with pytest.raises(ValueError, match="norm"):
        TransformerConfig(norm="batch")
    # a rotary model without rotary: the mha kind under rope="none"
    plain = TransformerConfig(n_layers=1, rope="none", dtype=jnp.float32)
    params = init_params(jax.random.PRNGKey(0), plain)
    tokens = jnp.arange(16, dtype=jnp.int32)[None] % 7
    turned = forward(params, tokens, dataclasses.replace(plain,
                                                         rope="rotary"))
    assert float(jnp.max(jnp.abs(forward(params, tokens, plain)
                                 - turned))) > 1e-4


def test_pp_tp_and_sp_refuse_the_kinds():
    """No sharded path is asked for: each refuses with what it cannot
    do, where the step is made or traced."""
    from jax.sharding import Mesh

    from ray_tpu.parallel.pipeline import make_pp_loss_fn
    cfg = _cfg()
    devices = np.array(jax.devices()[:2])
    with pytest.raises(ValueError, match="pipeline schedule"):
        make_pp_loss_fn(cfg, Mesh(devices, ("pp",)), n_micro=2)
    batch = {"tokens": jnp.asarray(_batches(1)[0])}
    params = _weights(1)
    for shape in ((1, 2, 1), (1, 1, 2)):
        mesh = Mesh(devices.reshape(shape), ("dp", "sp", "tp"))
        with pytest.raises(ValueError, match="no tp or sp layout"):
            jax.eval_shape(lambda p: loss_and_counters(p, batch, cfg, mesh),
                           params)
    # a mesh of dp alone is fine
    mesh = Mesh(devices.reshape(2, 1, 1), ("dp", "sp", "tp"))
    loss, _ = jax.eval_shape(
        lambda p: loss_and_counters(p, batch, cfg, mesh), params)
    assert loss.shape == ()


def test_the_shared_arrays_are_made_once_a_forward_and_kept():
    """In the differentiated step's jaxpr the scan output that is handed
    on leaves layer 16's scan once and enters layer 18's, no second
    selective scan is made for the reader, and the cross layer's scan
    holds no key or value projection."""
    cfg, params = _cfg(), _weights(2)
    batch = {"tokens": jnp.asarray(_batches(2)[0])}
    jaxpr = jax.make_jaxpr(lambda p: loss_fn(p, batch, cfg))(params)
    scans = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "scan"]
    assert len(scans) == 6
    memory = (2, 32, 128)

    def hands_on(eqn, shape):
        return [v for v in eqn.outvars if v.aval.shape == (1,) + shape]

    def takes(eqn, shape):
        return [v for v in eqn.invars
                if getattr(v.aval, "shape", None) == shape]

    # layer 16 (the third scan) stacks its scan output; layer 18 (the
    # fifth) takes it as a constant of its scan; layer 14 hands on none
    assert hands_on(scans[2], memory) and not hands_on(scans[0], memory)
    assert takes(scans[4], memory) and not takes(scans[5], memory)
    # layer 17 hands on k1, k2 [2, 32, 2, 8] and V [2, 32, 2, 16]
    assert len(hands_on(scans[3], (2, 32, 2, 8))) == 2
    assert len(hands_on(scans[3], (2, 32, 2, 16))) == 1
    assert takes(scans[5], (2, 32, 2, 16))
