"""The block-diffusion objective (``models/block_diffusion.py``) on the
grouped-K/V sparse-expert layer: the program against the benchmark's
plain reference at a tiny size, the collator's noise, and the dense
model's defaults held bitwise to what they computed before the new
fields existed."""

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from ray_tpu.models import block_diffusion  # noqa: E402
from ray_tpu.models.transformer import (  # noqa: E402
    TransformerConfig, init_params, loss_fn, make_train_state,
    make_train_step)

# 2 layers, 16 experts of which this rank holds 4 (experts 4-7), 8 query
# heads on 2 K/V heads, rows of 32 data tokens in blocks of 4.
CONFIG = {
    "reference": "block_diffusion_moe", "hidden_size": 64,
    "moe_intermediate_size": 32, "num_attention_heads": 8,
    "num_key_value_heads": 2, "head_dim": 16, "num_hidden_layers": 2,
    "vocab_size": 128, "num_experts": 16, "num_experts_per_tok": 4,
    "num_experts_held": 4, "experts_held_first": 4, "norm_topk_prob": True,
    "rms_norm_eps": 1e-6, "rope_theta": 1000000, "initializer_range": 0.02,
    "dtype": "float32", "remat": True,
    "router_aux_loss_coef": 0.0,
    "block_diffusion": {"block_length": 4, "t_min": 0.001,
                        "mask_token_id": 127},
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


def _program(seed, batches):
    """Two steps of ``make_train_step`` under the block-diffusion
    objective from the seed's weights -> what the reference returns."""
    from benchmarks.drivers import trainer_blockdiff_steps as driver
    from benchmarks.drivers.trainer_steps import _adam_mu, _leaf_norms
    from benchmarks.harness import moe_weights
    cfg = TransformerConfig(dtype=jnp.float32, **driver._model_kwargs(
        CONFIG, TRAFFIC["seq_len"]))
    state, tx = make_train_state(
        jax.random.PRNGKey(0), cfg,
        learning_rate=CONFIG["optimizer"]["learning_rate"])
    start = moe_weights.make_sparse_decoder(seed, CONFIG, jnp.float32)
    assert jax.tree.map(jnp.shape, start) == jax.tree.map(
        jnp.shape, state["params"])
    state["params"] = start
    step = make_train_step(cfg, tx, loss_override=functools.partial(
        block_diffusion.loss_fn, cfg=cfg, block=4))
    out = {"losses": [], "metrics": [], "choices": []}
    for i, batch in enumerate(batches):
        state, metrics = step(state, {k: jnp.asarray(v)
                                      for k, v in batch.items()})
        out["losses"].append(float(metrics["loss"]))
        out["choices"].append(np.asarray(metrics.pop("moe_choices")))
        out["metrics"].append({k: float(v) for k, v in metrics.items()})
        if i == 0:
            out["grad1_norm"] = {
                k: np.asarray(v, np.float64) / (1.0 - 0.9) for k, v in
                _leaf_norms(_adam_mu(state["opt"])).items()}
    again = moe_weights.make_sparse_decoder(seed, CONFIG, jnp.float32)
    out["change_norm"] = {k: np.asarray(v, np.float64) for k, v in
                          _leaf_norms(jax.tree.map(
                              lambda a, b: a - b, state["params"],
                              again)).items()}
    return out


def test_program_matches_the_plain_reference_and_the_fp8_control_fails():
    """(e) The whole loss, the first gradient leaf by leaf and the
    parameters' change over two AdamW steps."""
    from benchmarks.drivers import trainer_blockdiff_steps as driver
    from benchmarks.harness import compare
    seed = 2**31 + 5
    batches = driver.make_batches(CONFIG, TRAFFIC, seed)
    prog = _program(seed, batches)
    ref = driver.follow_reference(CELL, CONFIG, seed, batches,
                                  choices=prog["choices"])
    assert prog["choices"][0].shape == (2, 2, 64, 4)
    # the program's experts are the reference's own, or tied with them
    assert ref["routing_gap"][0] <= 1e-5, ref["routing_gap"]
    assert max(compare.loss_gaps(prog, ref)) <= LOSS_GAP
    correct, compared = compare.judge(compare.train_numbers(prog, ref),
                                      LIMITS)
    assert correct, compared
    # the step's counters: 2 rows x 64 positions x 4 choices, a quarter
    # of the experts held; nothing dropped; masked tokens as the batch's
    for metrics, batch in zip(prog["metrics"], batches):
        assert metrics["moe_dropped_choices"] == 0.0
        assert 0 < metrics["moe_held_choices"] < 2 * 64 * 4
        assert 0.25 <= metrics["moe_expert_load_max"] <= 1.0
        assert metrics["masked_tokens"] == float((batch["weight"] > 0).sum())
    # each control in the program's place, its experts followed likewise
    fp8 = driver.follow_reference(CELL, CONFIG, seed, batches,
                                  precision="fp8")
    ref = driver.follow_reference(CELL, CONFIG, seed, batches,
                                  choices=fp8["choices"])
    correct, compared = compare.judge(compare.train_numbers(fp8, ref), LIMITS)
    assert not correct, compared
    causal = driver.follow_reference(CELL, CONFIG, seed, batches,
                                     mask="causal")
    ref = driver.follow_reference(CELL, CONFIG, seed, batches,
                                  choices=causal["choices"])
    assert compare.train_numbers(causal, ref)["grad1_norm_gap"][0] > 0.1
    assert ref["routing_gap"][0] > 0.01


def test_noise_is_constant_over_a_block_and_weights_the_masked_alone():
    """(f) One t a block; a block's tokens are masked with probability
    t; the weight is 1/t on masked positions and 0 elsewhere."""
    tokens = jax.random.randint(jax.random.PRNGKey(0), (64, 1024), 1, 100)
    xt, weight = block_diffusion.noise(jax.random.PRNGKey(1), tokens,
                                       block=32, mask_id=127, t_min=1e-3)
    xt, weight, tokens = map(np.asarray, (xt, weight, tokens))
    masked = xt == 127
    assert xt.dtype == tokens.dtype and weight.dtype == np.float32
    assert (xt[~masked] == tokens[~masked]).all()
    assert ((weight > 0) == masked).all()
    t = 1.0 / weight[masked]
    assert t.min() >= 1e-3 and t.max() <= 1.0
    w = weight.reshape(64, 32, 32)
    share = masked.reshape(64, 32, 32).mean(-1)
    t_blocks, shares = [], []
    for row_w, row_share in zip(w.reshape(-1, 32), share.reshape(-1)):
        seen = np.unique(row_w[row_w > 0])
        assert len(seen) <= 1                      # one t a block
        if len(seen):
            t_blocks.append(1.0 / seen[0])
            shares.append(row_share)
    t_blocks, shares = np.array(t_blocks), np.array(shares)
    # 32 draws a block at probability t: the share follows t
    sigma = np.sqrt(t_blocks * (1 - t_blocks) / 32) + 1e-3
    assert np.mean(np.abs(shares - t_blocks) <= 4 * sigma) >= 0.995
    assert abs(np.mean(shares - t_blocks)) < 0.01
    assert np.corrcoef(shares, t_blocks)[0, 1] > 0.95
    # over all blocks the masked share is the mean of U(0.001, 1)
    assert abs(masked.mean() - 0.5005) < 0.02
    with pytest.raises(ValueError, match="does not divide"):
        block_diffusion.noise(jax.random.PRNGKey(1), tokens[:, :30], 4, 127)


# ---- (g) the dense model as it was before this module's fields ---------
# ``models/transformer.py`` and ``ops/ring_attention.full_attention`` at
# commit f0e646b, dense branch only, kept here word for word.

def _old_init_params(rng, cfg):
    k_embed, k_layers, k_head = jax.random.split(rng, 3)
    d, h, dh, f, nl = (cfg.d_model, cfg.n_heads, cfg.d_model // cfg.n_heads,
                       cfg.d_ff, cfg.n_layers)
    init = jax.nn.initializers.normal(0.02)
    lkeys = jax.random.split(k_layers, 6)

    def stacked(key, shape):
        return init(key, (nl,) + shape, jnp.float32).astype(cfg.dtype)

    layers = {
        "ln1": jnp.ones((nl, d), jnp.float32),
        "ln2": jnp.ones((nl, d), jnp.float32),
        "wq": stacked(lkeys[0], (d, h, dh)),
        "wk": stacked(lkeys[1], (d, h, dh)),
        "wv": stacked(lkeys[2], (d, h, dh)),
        "wo": stacked(lkeys[3], (h, dh, d)),
        "w1": stacked(lkeys[4], (d, f)),
        "w3": stacked(lkeys[5], (d, f)),
        "w2": stacked(jax.random.fold_in(k_layers, 7), (f, d)),
    }
    return {
        "embed": init(k_embed, (cfg.vocab_size, d), jnp.float32
                      ).astype(cfg.dtype),
        "layers": layers,
        "ln_f": jnp.ones((d,), jnp.float32),
        "lm_head": init(k_head, (d, cfg.vocab_size), jnp.float32
                        ).astype(cfg.dtype),
    }


def _old_rms_norm(x, w, eps=1e-5):
    xf = x.astype(jnp.float32)
    norm = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (norm * w).astype(x.dtype)


def _old_rope(x, positions, theta):
    d = x.shape[-1]
    half = d // 2
    freqs = jnp.exp(-jnp.log(theta) *
                    jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions[..., None].astype(jnp.float32) * freqs
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1).astype(x.dtype)


def _old_full_attention(q, k, v):
    scale = q.shape[-1] ** -0.5
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    li, lj = logits.shape[-2], logits.shape[-1]
    mask = jax.lax.broadcasted_iota(jnp.int32, (li, lj), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (li, lj), 1)
    logits = jnp.where(mask[None, None], logits, -jnp.inf)
    p = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32).astype(q.dtype)


def _old_loss_fn(params, batch, cfg):
    tokens = batch["tokens"]
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    B, S = inputs.shape
    x = jnp.take(params["embed"], inputs, axis=0)
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))

    def layer(carry, lp):
        x, aux = carry
        h = _old_rms_norm(x, lp["ln1"])
        q = jnp.einsum("bsd,dhk->bshk", h, lp["wq"])
        k = jnp.einsum("bsd,dhk->bshk", h, lp["wk"])
        v = jnp.einsum("bsd,dhk->bshk", h, lp["wv"])
        q = _old_rope(q, positions, cfg.rope_theta)
        k = _old_rope(k, positions, cfg.rope_theta)
        o = _old_full_attention(q, k, v)
        x = x + jnp.einsum("bshk,hkd->bsd", o, lp["wo"])
        h = _old_rms_norm(x, lp["ln2"])
        gate = jax.nn.silu(jnp.einsum("bsd,df->bsf", h, lp["w1"]))
        up = jnp.einsum("bsd,df->bsf", h, lp["w3"])
        x = x + jnp.einsum("bsf,fd->bsd", gate * up, lp["w2"])
        return (x, aux + jnp.zeros((), jnp.float32)), None

    layer_fn = jax.checkpoint(layer) if cfg.remat else layer
    (x, _), _ = jax.lax.scan(lambda c, lp: layer_fn(c, lp),
                             (x, jnp.zeros((), jnp.float32)),
                             params["layers"])
    x = _old_rms_norm(x, params["ln_f"])
    logits = jnp.einsum("bsd,dv->bsv", x, params["lm_head"])
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None],
                               axis=-1).squeeze(-1)
    return jnp.mean(logz - gold)


@pytest.mark.parametrize("dtype,remat", [(jnp.float32, True),
                                         (jnp.bfloat16, True),
                                         (jnp.float32, False)])
def test_defaults_leave_the_dense_model_bitwise_as_it_was(dtype, remat):
    """(g) No new field set: the same parameter tree from the same key,
    the same loss and the same gradients, bit for bit."""
    cfg = TransformerConfig(vocab_size=256, d_model=64, n_layers=2,
                            n_heads=4, d_ff=96, max_seq_len=64, dtype=dtype,
                            remat=remat)
    assert (cfg.n_kv_heads, cfg.head_dim, cfg.qk_norm, cfg.norm_eps) == (
        4, 16, False, 1e-5)
    params = init_params(jax.random.PRNGKey(3), cfg)
    old = _old_init_params(jax.random.PRNGKey(3), cfg)
    assert jax.tree.structure(params) == jax.tree.structure(old)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(old)):
        assert a.dtype == b.dtype and np.array_equal(np.asarray(a),
                                                     np.asarray(b))
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(4), (2, 65),
                                          0, 256, dtype=jnp.int32)}
    got = jax.jit(jax.value_and_grad(
        lambda p: loss_fn(p, batch, cfg)))(params)
    want = jax.jit(jax.value_and_grad(
        lambda p: _old_loss_fn(p, batch, cfg)))(params)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.dtype == b.dtype
        assert np.array_equal(np.asarray(a.astype(jnp.float32)),
                              np.asarray(b.astype(jnp.float32)))
    # and the step reports what it reported: nothing new for a dense model
    state, tx = make_train_state(jax.random.PRNGKey(3), cfg)
    _, metrics = make_train_step(cfg, tx)(state, batch)
    assert set(metrics) == {"loss", "grad_norm"}


def _dense_objective(remat):
    cfg = TransformerConfig(vocab_size=256, d_model=64, n_layers=2,
                            n_heads=4, d_ff=96, max_seq_len=128,
                            dtype=jnp.float32, remat=remat)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(4), (2, 129),
                                          0, 256, dtype=jnp.int32)}
    return cfg, lambda p: loss_fn(p, batch, cfg)


def _block_diffusion_objective(remat):
    """The expert stack (8 experts, 4 held, top 2; 4 query heads on 2
    K/V heads) under the block-diffusion objective: rows of 128 tokens
    run as 256 positions."""
    cfg = TransformerConfig(vocab_size=128, d_model=64, n_layers=2,
                            n_heads=4, n_kv_heads=2, head_dim=16,
                            qk_norm=True, d_ff=32, max_seq_len=256,
                            dtype=jnp.float32, remat=remat, moe_experts=8,
                            moe_top_k=2, moe_experts_held=(2, 4))
    x0 = jax.random.randint(jax.random.PRNGKey(4), (2, 128), 0, 127,
                            dtype=jnp.int32)
    xt, weight = block_diffusion.noise(jax.random.PRNGKey(5), x0, 4, 127)
    batch = {"tokens": x0, "noisy": xt, "weight": weight}
    return cfg, lambda p: block_diffusion.loss_fn(p, batch, cfg, 4)[0]


@pytest.mark.parametrize("attention", ["reference", "kernel"])
@pytest.mark.parametrize("objective", [_dense_objective,
                                       _block_diffusion_objective],
                         ids=["dense", "experts-blockdiff"])
def test_remat_that_keeps_the_flash_residuals_changes_no_number(
        objective, attention, monkeypatch):
    """``remat=True`` (the layer's input and, where the kernel runs, its
    ``out`` and ``lse`` kept; the rest recomputed) against
    ``remat=False``: the same loss and gradients.  With the kernel in the
    model (interpreted here; the chip's dispatch) the gradient holds the
    forward kernel once: the policy reaches it through ``run_layers``."""
    if attention == "kernel":
        from ray_tpu.models import mha
        from ray_tpu.ops.flash_attention import flash_attention
        monkeypatch.setattr(mha, "flash_or_ref_attention",
                            functools.partial(flash_attention,
                                              interpret=True))
    got = {}
    for remat in (True, False):
        cfg, loss = objective(remat)
        params = init_params(jax.random.PRNGKey(3), cfg)
        fn = jax.value_and_grad(loss)
        text = str(jax.make_jaxpr(fn)(params))
        assert text.count("name=flash_attention_fwd") == text.count(
            "name=flash_attention_bwd") == (attention == "kernel")
        got[remat] = jax.jit(fn)(params)
    assert float(got[True][0]) == float(got[False][0])
    for a, b in zip(jax.tree.leaves(got[True][1]),
                    jax.tree.leaves(got[False][1])):
        assert bool(jnp.all(jnp.isfinite(a)))
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-7)


def test_the_spans_and_counters_of_the_objective_have_readers():
    """``train.noise`` around the collator's draw; the step's counters,
    where a worker reports them, as gauges on /metrics; the two
    block-diffusion roofline readers find nothing in a trace without
    the kernels (the parent's program) and a share where they are."""
    from benchmarks import run as bench_run
    from ray_tpu._private.metrics_agent import get_metrics_registry
    from ray_tpu.train.session import Session
    from ray_tpu.util import tracing
    tracing.clear()
    tracing.enable(True)
    try:
        tokens = jnp.ones((2, 8), jnp.int32)
        block_diffusion.noise(jax.random.PRNGKey(0), tokens, 4, 127)
        names = [e["name"] for e in tracing.chrome_tracing_dump()]
    finally:
        tracing.enable(False)
        tracing.clear()
    assert names.count("train.noise") == 1

    session = Session(lambda: None, 3, 0, 4)
    # every number a worker reports, and nothing that is not a number
    session.report(loss=1.0, moe_held_choices=123.0, moe_expert_load_max=0.5,
                   moe_dropped_choices=0.0, masked_tokens=7.0,
                   note="warm", resumed=True)
    exposed = get_metrics_registry().render_prometheus().splitlines()
    assert 'ray_tpu_train_moe_held_choices{rank="3"} 123.0' in exposed
    assert 'ray_tpu_train_masked_tokens{rank="3"} 7.0' in exposed
    assert 'ray_tpu_train_moe_dropped_choices{rank="3"} 0.0' in exposed
    assert not any("train_note" in line or "train_resumed" in line
                   for line in exposed)

    ctx = {"trace": {"device_ops": {"/device:TPU:0": [
        ["fusion.1", 0.0, 5e6]]}, "host_spans": []},
        "facts": {"rows": 4, "seq_len": 4096}, "device_kind": "TPU v5 lite",
        "config": {"hidden_size": 2048, "num_attention_heads": 32,
                   "num_key_value_heads": 4, "head_dim": 128,
                   "block_diffusion": {"block_length": 4}}}
    fwd = bench_run._reader("blockdiff_flash_fwd_roofline")
    bwd = bench_run._reader("blockdiff_flash_bwd_roofline")
    assert fwd(ctx) is None and bwd(ctx) is None
    # one forward call of 55.87 ms and one backward of 22.35 ms: 1.1007
    # and 2.2014 TFLOP at 197 TFLOP/s are 5.587 and 11.175 ms
    ctx["trace"]["device_ops"]["/device:TPU:0"] += [
        ["flash_attention_fwd.25", 1e7, 55.87e6],
        ["flash_attention_bwd.11", 1e8, 22.35e6]]
    assert fwd(ctx) == pytest.approx(10.0, rel=1e-3)
    assert bwd(ctx) == pytest.approx(50.0, rel=1e-3)
    dense = dict(ctx, config={"hidden_size": 2048})
    assert fwd(dense) is None
