"""Single-chip model benchmark: flagship transformer train step MFU.

The scheduler bench (bench.py) covers the runtime's TPU kernel; this
covers the MODEL compute path — ``ray_tpu.models.transformer`` with
flash attention and rematerialisation — at a realistic single-chip size,
reporting step time, achieved FLOP/s and MFU against the chip's peak.

FLOP accounting (standard: Chowdhery et al. PaLM appendix B):
  train_step ≈ 6 * n_params * n_tokens      (fwd 2x + bwd 4x matmuls)
             + 12 * n_layers * B * S^2 * d  (attention scores+values,
                                             fwd+bwd, causal halves it)

Prints ONE JSON line:
  {"metric": "transformer_train_step_mfu", "value": <mfu %>, ...}
MFU is a device metric: a device whose kind is not in ``_PEAK_TFLOPS``
(the CPU included) is an error, not a default peak.
"""

import json
import sys
import time


# Peak dense bf16 FLOP/s per CHIP, keyed by the exact ``device_kind``
# the runtime reports (public spec sheets; "TPU v5 lite" is what the
# installed libtpu calls a v5e: 197 TFLOP/s, Google Cloud "TPU v5e").
_PEAK_TFLOPS = {
    "TPU v2": 45.0,
    "TPU v3": 123.0,
    "TPU v4": 275.0,
    "TPU v4 lite": 137.0,
    "TPU v5 lite": 197.0,
    "TPU v5e": 197.0,
    "TPU v5p": 459.0,
    "TPU v6e": 918.0,
    "TPU v6 lite": 918.0,
}

#: The single-chip model configuration (chip_smoke.py trains the same
#: one through ray_tpu.train.Trainer): kwargs of TransformerConfig
#: minus the dtype, plus the batch.
TPU_MODEL = dict(vocab_size=32_000, d_model=1024, n_layers=8, n_heads=16,
                 d_ff=4096, max_seq_len=1024, remat=True)
TPU_BATCH, TPU_SEQ = 8, 1024


def chip_peak_tflops(device) -> float:
    kind = device.device_kind
    if kind not in _PEAK_TFLOPS:
        raise ValueError(
            f"no peak FLOP/s on record for device_kind {kind!r} "
            f"(platform {device.platform!r}); MFU is measured on a "
            f"known accelerator only — add the kind to _PEAK_TFLOPS "
            f"with its source")
    return _PEAK_TFLOPS[kind]


def main():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models.transformer import (TransformerConfig,
                                            make_train_state,
                                            make_train_step)

    device = jax.devices()[0]
    peak = chip_peak_tflops(device)      # raises before any work
    cfg = TransformerConfig(dtype=jnp.bfloat16, **TPU_MODEL)
    batch_size, seq_len, reps = TPU_BATCH, TPU_SEQ, 10

    state, tx = make_train_state(jax.random.PRNGKey(0), cfg)
    train_step = make_train_step(cfg, tx)    # jitted, donates state

    rng = np.random.default_rng(0)
    batch = {
        # loss_fn shifts internally: [B, S+1] tokens.
        "tokens": jnp.asarray(rng.integers(
            0, cfg.vocab_size, (batch_size, seq_len + 1)), jnp.int32),
    }

    n_params = sum(int(np.prod(x.shape))
                   for x in jax.tree_util.tree_leaves(state["params"]))

    # Warmup/compile + correctness signal.
    state, metrics = train_step(state, batch)
    jax.block_until_ready(metrics)
    loss0 = float(metrics["loss"])
    assert np.isfinite(loss0), "non-finite loss"

    t0 = time.perf_counter()
    for _ in range(reps):
        state, metrics = train_step(state, batch)
    jax.block_until_ready(metrics)
    step_s = (time.perf_counter() - t0) / reps

    n_tokens = batch_size * seq_len
    flops = 6.0 * n_params * n_tokens + \
        12.0 * cfg.n_layers * batch_size * seq_len ** 2 * cfg.d_model / 2
    achieved_tflops = flops / step_s / 1e12
    mfu = achieved_tflops / peak * 100.0

    print(json.dumps({
        "metric": "transformer_train_step_mfu",
        "value": round(mfu, 2),
        "unit": "%",
        "vs_baseline": round(mfu / 40.0, 2),   # target: >= 40% MFU
        "step_ms": round(step_s * 1000.0, 2),
        "achieved_tflops": round(achieved_tflops, 2),
        "peak_tflops": peak,
        "device_kind": device.device_kind,
        "backend": device.platform,
        "params_m": round(n_params / 1e6, 1),
        "tokens_per_step": n_tokens,
        "loss_after_warmup": round(loss0, 4),
        "config": {"d_model": cfg.d_model, "n_layers": cfg.n_layers,
                   "n_heads": cfg.n_heads, "d_ff": cfg.d_ff,
                   "batch": batch_size, "seq": seq_len},
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
