"""What more than one kind of layer needs: the two records a kind's
module is written against (``LayerKind``, ``LayerCall``), the model's
norms, the rotary table; and the one kind too small for a file, the
dense SwiGLU.  Everything under ``models/`` imports from here and this
file imports none of it."""

from __future__ import annotations

import contextlib
import dataclasses
import math
from typing import Any, Callable, Dict, Mapping, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from ray_tpu.ops.attention_mask import CAUSAL


@dataclasses.dataclass(frozen=True)
class LayerCall:
    """What a layer is given beside its input and its parameters."""
    cfg: Any                  # the TransformerConfig
    #: The run's first word as the pattern has it, and its options.
    run: str = ""
    options: Mapping = dataclasses.field(default_factory=dict)
    positions: Any = None
    mesh: Any = None
    mask: Any = CAUSAL
    #: The layer's index in the model; the slots written so far, by name.
    index: Any = None
    shared: Optional[Dict] = None


@dataclasses.dataclass(frozen=True)
class LayerKind:
    """A kind of attention or of FFN, as ``models/kinds.py`` lists it.
    A kind refuses what it cannot take itself."""
    name: str
    #: ``init(key, n_layers, cfg, options)`` -> the kind's stacked leaves
    #: as they lie in a layer's tree, drawn from the stack's ``key``.
    init: Callable
    #: ``specs(cfg, options)`` -> their PartitionSpecs, the same tree.
    specs: Callable
    #: ``apply(h, lp, call)`` on the layer's normed input and whole tree
    #: -> (what it adds to the residual, what it counted, what it hands
    #: on: None, or the value of the slot its run writes).  None (an FFN
    #: kind): the layer is its mixer alone, with no second norm.
    apply: Optional[Callable]
    #: What a run may say after the kind (``kinds.run_options``: a whole
    #: number, a name, or one of the slots named), and what it implies.
    options: Mapping = dataclasses.field(default_factory=dict)
    implied: Mapping = dataclasses.field(default_factory=dict)
    #: The field of the configuration that has to be set for the kind.
    needs: Optional[str] = None
    #: It runs on one device's rows whole: no ``tp``, ``sp`` or ``pp``.
    single_device: bool = False
    #: Its layers read their index in the model.
    indexed: bool = False
    #: ``check(call)`` refuses a run the configuration, or the mesh the
    #: state is laid out for (``call.mesh``), cannot hold.
    check: Callable = lambda call: None
    #: ``run_scope(options)`` -> the scope a run's attention half opens
    #: beside ``attention`` for the device trace.
    run_scope: Callable = lambda options: contextlib.nullcontext()


def replicated(init: Callable) -> Callable:
    """-> the ``specs`` of a kind that has no ``tp`` layout yet: every
    leaf ``init`` draws, replicated."""
    return lambda cfg, options: jax.tree.map(lambda _: P(), jax.eval_shape(
        lambda: init(jax.random.PRNGKey(0), 1, cfg, options)))


def on_one_device(call: LayerCall) -> None:
    """A ``single_device`` kind's refusals: another objective's mask, a
    mesh that splits heads or positions."""
    kind = call.run.partition(":")[0]
    if call.mask != CAUSAL:
        raise ValueError(f"a {kind!r} layer brings its own mask")
    if call.mesh is not None and max(call.mesh.shape.get("tp", 1),
                                     call.mesh.shape.get("sp", 1)) > 1:
        raise ValueError(f"a {kind!r} layer has no tp or sp layout")


@dataclasses.dataclass(frozen=True)
class RopeTable:
    """One rotary table: what a run of the ``mha`` kind turns q and k by
    (``TransformerConfig.rope_tables``, named by the run's ``rope=``).
    ``factor`` > 1 is YaRN (arXiv:2309.00071): with ``d`` the rotated
    columns and ``f_i = theta ** (-2 i / d)``, the frequencies whose
    wavelength the ``original_max_position`` positions hold fewer than
    ``beta_slow`` times are divided by ``factor``, those they hold more
    than ``beta_fast`` times stay, a linear ramp between; cos and sin
    are multiplied by ``attention_factor``."""
    theta: float = 10_000.0
    #: >0: the first ``rotary_dim`` columns of a head turn (the halves
    #: of that slice), the others pass.
    rotary_dim: int = 0
    factor: float = 1.0
    original_max_position: int = 0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0

    def __post_init__(self):
        if self.factor != 1.0 and self.original_max_position < 1:
            raise ValueError("a scaled rotary table says which positions "
                             "it was first trained for")

    def frequencies(self, width: int):
        """-> the ``width // 2`` angles a position, float32."""
        half = width // 2
        if self.factor == 1.0:
            return jnp.exp(-jnp.log(self.theta) *
                           jnp.arange(0, half, dtype=jnp.float32) / half)
        i = np.arange(half, dtype=np.float64)
        plain = self.theta ** (-i / half)

        def turns_at(turns):
            # the index whose wavelength the first positions hold
            # ``turns`` times
            return width * math.log(self.original_max_position / (
                2 * math.pi * turns)) / (2 * math.log(self.theta))

        low = max(math.floor(turns_at(self.beta_fast)), 0)
        high = min(math.ceil(turns_at(self.beta_slow)), width - 1)
        ramp = np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
        return jnp.asarray(plain / self.factor * ramp + plain * (1.0 - ramp),
                           jnp.float32)


def _rope(x, positions, table: RopeTable):
    # x: [B, S, H, D]; rotate pairs: of all D columns, or of the first
    # ``table.rotary_dim`` with the others passed through.
    rotary_dim = table.rotary_dim
    if rotary_dim and rotary_dim < x.shape[-1]:
        return jnp.concatenate(
            [_rope(x[..., :rotary_dim], positions,
                   dataclasses.replace(table, rotary_dim=0)),
             x[..., rotary_dim:]], axis=-1)
    d = x.shape[-1]
    half = d // 2
    freqs = table.frequencies(d)
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B, S, half]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    if table.attention_factor != 1.0:
        cos, sin = cos * table.attention_factor, sin * table.attention_factor
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1).astype(x.dtype)


def _rms_norm(x, w, eps):
    xf = x.astype(jnp.float32)
    norm = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (norm * w).astype(x.dtype)


def _layer_norm(x, w, b, eps):
    xf = x.astype(jnp.float32)
    xf = xf - jnp.mean(xf, axis=-1, keepdims=True)
    norm = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (norm * w + b).astype(x.dtype)


def model_norm(x, tree: Dict, name: str, cfg):
    """The model's norm ``name`` (``ln1``, ``ln2``, ``ln_f``) of
    ``tree``: RMSNorm, or LayerNorm with the bias ``<name>_b``."""
    if cfg.norm == "layernorm":
        return _layer_norm(x, tree[name], tree[name + "_b"], cfg.norm_eps)
    return _rms_norm(x, norm_weight(tree[name], cfg), cfg.norm_eps)


def norm_weight(w, cfg):
    """A model norm's weight as it scales: ``1 + w`` under
    ``norm_plus_one``."""
    return w + 1.0 if cfg.norm_plus_one else w


def stacked_normal(n_layers: int, dtype):
    """-> ``stacked(key, shape)``: a matrix a layer, N(0, 0.02) drawn in
    float32."""
    init = jax.nn.initializers.normal(0.02)
    return lambda key, shape: init(key, (n_layers, *shape),
                                   jnp.float32).astype(dtype)


def norm_start(cfg, shape):
    """A model norm's weight at the start: 1, or 0 where it scales by
    ``1 + w``."""
    return (jnp.zeros if cfg.norm_plus_one else jnp.ones)(shape, jnp.float32)


def _dense_init(key: jax.Array, n_layers: int, cfg, options: Dict) -> Dict:
    d, f = cfg.d_model, cfg.d_ff
    lkeys = jax.random.split(key, 6)     # (``mha`` draws 0 to 3)
    stacked = stacked_normal(n_layers, cfg.dtype)
    return {"w1": stacked(lkeys[4], (d, f)),
            "w3": stacked(lkeys[5], (d, f)),
            "w2": stacked(jax.random.fold_in(key, 7), (f, d))}


def _dense_ffn(h, lp: Dict, call: LayerCall):
    gate = jax.nn.silu(checkpoint_name(
        jnp.einsum("bsd,df->bsf", h, lp["w1"]), "ffn_gate"))
    up = checkpoint_name(jnp.einsum("bsd,df->bsf", h, lp["w3"]), "ffn_up")
    return jnp.einsum("bsf,fd->bsd", gate * up, lp["w2"]), {}, None


#: The ``"dense"`` kind of FFN: ``w1``/``w3 [d, d_ff]`` and ``w2 [d_ff,
#: d]`` flat in the layer's tree, the hidden width over ``tp``.
DENSE = LayerKind(
    "dense", _dense_init,
    lambda cfg, options: {"w1": P(None, None, "tp"), "w3": P(None, None, "tp"),
                          "w2": P(None, "tp", None)}, _dense_ffn)
#: The ``"none"`` kind of FFN: a layer that is a mixer alone (Nemotron-H's
#: ``M`` before an attention layer): no leaves, no second norm, nothing
#: added.
NONE = LayerKind("none", lambda key, n_layers, cfg, options: {},
                 lambda cfg, options: {}, None)
