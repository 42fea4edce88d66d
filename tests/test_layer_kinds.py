"""The seam between ``models/transformer.py`` and the kinds of layer
(``models/kinds.py``: one ``LayerKind`` record a kind, in the kind's own
module): every record keeps the one signature, the parameter trees of the
six tiny configurations are the ones the tree before the records drew,
bit for bit, and the imports under ``models/`` point one way."""

import ast
import hashlib
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.common import LayerCall
from ray_tpu.models.gdn import GDNConfig
from ray_tpu.models.kinds import ATTENTION, FFN, run_options
from ray_tpu.models.mamba import MambaConfig
from ray_tpu.models.kda import KDAConfig
from ray_tpu.models.mamba2 import Mamba2Config
from ray_tpu.models.mla import MLAConfig
from ray_tpu.models.transformer import TransformerConfig, init_params
from ray_tpu.ops.attention_mask import FULL

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_BASE = dict(vocab_size=64, d_model=32, n_heads=4, n_kv_heads=2, d_ff=48,
             max_seq_len=16, dtype=jnp.float32)
_MAMBA = MambaConfig(d_inner=64, d_state=4, d_conv=4, dt_rank=4, chunk=8)
# kind -> (the run's first word, what the configuration needs for it)
RUNS = {
    "mha": ("mha:heads=6,window=4", {}),
    "mla": ("mla", dict(mla=MLAConfig(
        q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16))),
    "gdn": ("gdn", dict(gdn=GDNConfig(
        num_key_heads=2, num_value_heads=4, key_head_dim=8,
        value_head_dim=8, chunk=8))),
    "mamba": ("mamba:writes=memory", dict(mamba=_MAMBA)),
    "gmu": ("gmu", dict(mamba=_MAMBA)),
    "diff": ("diff:window=4,writes=kv", {}),
    "mamba2": ("mamba2", dict(mamba2=Mamba2Config(
        num_heads=4, head_dim=8, n_groups=2, state_size=8, chunk=8,
        norm_groups=2))),
    "kda": ("kda", dict(kda=KDAConfig(num_heads=2, head_dim=16, chunk=16))),
    "dense": ("mha", {}),
    "moe": ("mha", dict(moe_experts=4, moe_top_k=2, moe_shared_width=16)),
}


def _setting(kind):
    """-> (the kind's record, cfg, the call one of its layers gets)."""
    run, needs = RUNS[kind]
    pattern = [(run, "moe" if kind == "moe" else "dense", 2)]
    if kind == "gmu":
        pattern.insert(0, ("mamba:writes=memory", "dense", 1))
    cfg = TransformerConfig(layer_pattern=tuple(pattern), **_BASE, **needs)
    positions = jnp.broadcast_to(jnp.arange(16, dtype=jnp.int32)[None],
                                 (2, 16))
    call = LayerCall(cfg, run, run_options(run)[1], positions, index=3,
                     shared={"memory": jnp.full((2, 16, 64), 0.5)})
    return (FFN if kind in FFN else ATTENTION)[kind], cfg, call


@pytest.mark.parametrize("kind", sorted(RUNS))
def test_a_record_keeps_the_one_signature(kind):
    """``init`` and ``specs`` give trees of one structure, a spec a leaf
    and no longer than the leaf is deep; ``apply`` on each
    layer of a two-layer stack returns what the layer adds to the
    residual, a dict of what it counted, and something to hand on if
    and only if its run writes a slot."""
    record, cfg, call = _setting(kind)
    assert record.name == kind
    options = call.options if kind in ATTENTION else {}
    stack = record.init(jax.random.PRNGKey(1), 2, cfg, options)
    specs = record.specs(cfg, options)
    is_spec = lambda x: isinstance(x, jax.sharding.PartitionSpec)  # noqa: E731
    assert jax.tree.structure(jax.tree.map(lambda a: 0, stack)) == \
        jax.tree.structure(jax.tree.map(lambda s: 0, specs, is_leaf=is_spec))
    for leaf, spec in zip(jax.tree.leaves(stack),
                          jax.tree.leaves(specs, is_leaf=is_spec)):
        assert leaf.shape[0] == 2 and len(spec) <= leaf.ndim
    h = jax.random.normal(jax.random.PRNGKey(2), (2, 16, 32), jnp.float32)
    for layer in range(2):
        lp = jax.tree.map(lambda a: a[layer], stack)
        y, counted, handed_on = record.apply(h, lp, call)
        assert y.shape == h.shape and bool(jnp.all(jnp.isfinite(y)))
        assert isinstance(counted, dict)
        assert (handed_on is not None) == ("writes" in options)


@pytest.mark.parametrize("kind", sorted(
    k for k in RUNS if (FFN if k in FFN else ATTENTION)[k].needs))
def test_a_kind_is_refused_without_the_field_it_needs(kind):
    record, cfg, call = _setting(kind)
    unset = {record.needs: TransformerConfig().__dict__[record.needs]}
    with pytest.raises(ValueError, match=f'"{kind}" layer needs'):
        TransformerConfig(**{**_BASE, **RUNS[kind][1], **unset,
                             "layer_pattern": cfg.layer_pattern[-1:]})


@pytest.mark.parametrize("kind", sorted(
    k for k, record in ATTENTION.items() if record.single_device))
def test_a_single_device_kind_refuses_a_mask_and_a_split_mesh(kind):
    import dataclasses

    class Mesh:
        shape = {"dp": 2, "tp": 2}

    record, cfg, call = _setting(kind)
    lp = jax.tree.map(lambda a: a[0], record.init(
        jax.random.PRNGKey(1), 1, cfg, call.options))
    h = jnp.zeros((2, 16, 32))
    with pytest.raises(ValueError, match="brings its own mask"):
        record.apply(h, lp, dataclasses.replace(call, mask=FULL))
    with pytest.raises(ValueError, match="no tp or sp layout"):
        record.apply(h, lp, dataclasses.replace(call, mesh=Mesh()))


def _tiny(name):
    """The six configurations' tiny forms, as the model suites build
    them."""
    import importlib
    if name == "dense":
        return TransformerConfig()
    tiny = importlib.import_module({
        "block_diffusion": "test_block_diffusion",
        "latent": "test_mla_moe_mtp", "hybrid": "test_qwen3_next",
        "sambay": "test_phi4_flash", "windowed": "test_laguna"}[name])
    if name != "block_diffusion":
        return tiny._cfg()
    from benchmarks.drivers import trainer_blockdiff_steps as driver
    return TransformerConfig(dtype=jnp.float32, **driver._model_kwargs(
        tiny.CONFIG, tiny.TRAFFIC["seq_len"]))


# sha256 over every leaf's path, shape, dtype and bytes of
# ``init_params(PRNGKey(0), cfg)``, recorded on the tree of commit 0ba6bad
# (PR 44), before any code moved into the kinds' modules.
PARENT_PARAMS = {
    "dense": "e6665ae97ef484dd3c44439812cc40969901c5c8b1f69ffb4080e6c415d1329d",
    "block_diffusion":
        "e8fcb691792112999f6c63bde8d2964ad839a3562358b5d660e883a80f8df9f4",
    "latent":
        "da6ad62d612a91a23e00f1efc3b8beb36c3570b97930d3f961bc1167cf93925b",
    "hybrid":
        "1f558240e7b304ddeb6d687a37d63d6ac68409cf8890e6ee9cf8a1626ec59c50",
    "sambay":
        "b16bd5e870ca68542f1a16e02eb0478b1a9fe61f4c88b701a4945a19ff822079",
    "windowed":
        "c5e2928063ccc2e2338da9424d15bd5e9c61b1e05a5014233d2bae9c740c329e",
}


@pytest.mark.parametrize("name", sorted(PARENT_PARAMS))
def test_the_parameter_tree_is_the_parents_leaf_for_leaf(name):
    """The same paths, shapes, dtypes and values from the same key: the
    ``fold_in`` constants and the order of the ``split`` moved with the
    code that draws from them."""
    digest = hashlib.sha256()
    params = init_params(jax.random.PRNGKey(0), _tiny(name))
    for path, leaf in jax.tree_util.tree_leaves_with_path(params):
        a = np.asarray(leaf)
        digest.update(f"{jax.tree_util.keystr(path)} {a.shape} {a.dtype}\n"
                      .encode())
        digest.update(a.tobytes())
    assert digest.hexdigest() == PARENT_PARAMS[name]


# Who may import whom under ``models/``: a module imports only modules of
# a lower level.
LEVELS = {"common": 0, "remat": 0,
          "mha": 1, "mla": 1, "gdn": 1, "mamba": 1, "mamba2": 1, "kda": 1,
          "diff_attention": 1,
          "moe": 1, "kinds": 2, "transformer": 3,
          "mtp": 4, "block_diffusion": 4, "pipeline": 4, "__init__": 4}


def _model_imports(tree):
    """-> [(the ``ray_tpu.models`` / pipeline module imported, whether
    inside a function)] of a parsed file."""
    found = []

    def walk(node, inside):
        for child in ast.iter_child_nodes(node):
            names = []
            if isinstance(child, ast.ImportFrom) and child.module:
                names = [child.module] if child.module != "ray_tpu.models" \
                    else [f"ray_tpu.models.{a.name}" for a in child.names]
            elif isinstance(child, ast.Import):
                names = [a.name for a in child.names]
            found.extend((n.rsplit(".", 1)[1], inside) for n in names
                         if n.startswith("ray_tpu.models.")
                         or n == "ray_tpu.parallel.pipeline")
            walk(child, inside or isinstance(
                child, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)))

    walk(tree, False)
    return found


def test_the_arrows_under_models_point_one_way():
    """No kind's module, and neither ``common.py`` nor ``kinds.py``,
    imports ``transformer`` (or anything above itself), at the top of the
    file or inside a function; ``transformer.py`` imports no
    ``ray_tpu.models`` module inside a function; every file under
    ``models/`` has its level."""
    folder = os.path.join(ROOT, "ray_tpu", "models")
    files = {name[:-3]: os.path.join(folder, name)
             for name in sorted(os.listdir(folder)) if name.endswith(".py")}
    files["pipeline"] = os.path.join(ROOT, "ray_tpu", "parallel",
                                     "pipeline.py")
    assert set(files) == set(LEVELS)
    for module, path in files.items():
        with open(path) as f:
            imports = _model_imports(ast.parse(f.read()))
        for imported, inside in imports:
            assert LEVELS[imported] < LEVELS[module], (module, imported)
            assert not (inside and module == "transformer"), imported
        if module == "transformer":
            assert {"common", "kinds"} <= {name for name, _ in imports}
