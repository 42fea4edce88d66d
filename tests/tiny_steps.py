"""What the tests of the remat plan and of the program's spans share:
the eight tiny steps, as the benchmark's drivers build them, and a stand-in
for the device's memory.  A plain module: it holds no test."""

import functools

import pytest

from ray_tpu.models import remat

ROOM = (1 << 44, 0)          # a device with room for everything
FULL = (1 << 20, 1 << 20)    # ... and one that is full already


@pytest.fixture(autouse=True)
def every_candidate_that_spares_anything(monkeypatch):
    """At these widths (32 columns) no product is dearer to make again
    than an array is to keep (``_KEPT_BYTE_MOVES``: that takes some 500
    columns in bfloat16), so the tests order and keep whatever spares
    any work at all; ``test_a_name_has_to_spare_more_than_keeping_it_
    costs`` holds the threshold itself, at a cell's widths."""
    monkeypatch.setattr(remat, "_KEPT_BYTE_MOVES", 0.0)


def _device(monkeypatch, memory):
    monkeypatch.setattr(remat, "device_memory", lambda mesh=None: memory)


RUNS = {"dense": ["mha+dense"], "block_diffusion": ["mha+moe"],
        "latent": ["mla+dense", "mla+moe", "mla+moe"],
        "hybrid": ["gdn+moe", "mha+moe"],
        "sambay": ["diff:reads=kv+dense", "diff:window=8+dense",
                   "diff:writes=kv+dense", "gmu+dense", "mamba+dense",
                   "mamba:writes=memory+dense"],
        "windowed": ["mha:heads=6,rope=global+dense",
                     "mha:heads=6,rope=global+moe",
                     "mha:heads=9,window=8,rope=local+moe"],
        "nemotron": ["mamba2+moe", "mamba2+moe", "mamba2+none", "mha+moe"],
        "ling": ["kda+dense", "kda+moe", "mla+moe"]}


def _tiny_step(kind):
    """-> (step, state, batch) of one of the eight tiny configurations the
    tests of the models build, as the benchmark's drivers build them."""
    import importlib

    import jax
    import jax.numpy as jnp

    from ray_tpu.models.transformer import (TransformerConfig,
                                            make_train_state,
                                            make_train_step)
    over = None
    if kind == "dense":
        cfg = TransformerConfig(vocab_size=128, d_model=64, n_layers=2,
                                n_heads=4, d_ff=96, max_seq_len=32,
                                dtype=jnp.float32, remat=True)
        batch = {"tokens": jnp.zeros((2, 33), jnp.int32)}
    elif kind == "block_diffusion":
        import test_block_diffusion as tiny
        from benchmarks.drivers import trainer_blockdiff_steps as driver
        from ray_tpu.models import block_diffusion
        cfg = TransformerConfig(dtype=jnp.float32, **driver._model_kwargs(
            tiny.CONFIG, tiny.TRAFFIC["seq_len"]))
        over = functools.partial(block_diffusion.loss_fn, cfg=cfg, block=4)
        batch = {k: jnp.asarray(v) for k, v in driver.make_batches(
            tiny.CONFIG, tiny.TRAFFIC, 7)[0].items()}
    elif kind == "latent":
        import test_mla_moe_mtp as tiny
        from ray_tpu.models import mtp
        cfg = tiny._cfg()
        over = functools.partial(mtp.loss_fn, cfg=cfg, coeff=0.3)
        batch = {"tokens": jnp.asarray(tiny._batches(3)[0])}
    elif kind in ("sambay", "windowed", "nemotron", "ling"):
        tiny = importlib.import_module({
            "sambay": "test_phi4_flash", "windowed": "test_laguna",
            "nemotron": "test_nemotron_h", "ling": "test_ling"}[kind])
        cfg = tiny._cfg()
        batch = {"tokens": jnp.asarray(tiny._batches(3)[0])}
    else:
        import test_qwen3_next as tiny
        cfg = tiny._cfg()
        batch = {"tokens": jnp.asarray(tiny._batches(3)[0])}
    state, tx = make_train_state(jax.random.PRNGKey(1), cfg)
    return make_train_step(cfg, tx, loss_override=over), state, batch
