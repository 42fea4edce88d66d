"""Flagship model: a decoder-only transformer, TPU-first.

Design notes (this is the model the framework's Train library and the
graft entry exercise):
  * Pure functional jax — params are a pytree of arrays, the whole train
    step is one ``jit`` over a global ``Mesh``; XLA/GSPMD inserts all
    collectives from the shardings (no hand-written allreduce, unlike the
    reference's Train/torch DDP backend, ``python/ray/train/torch.py``).
  * Megatron-style tensor parallelism over ``tp`` (heads + FFN hidden
    sharded), data parallel over ``dp``, context parallel over ``sp``
    via ring attention (ops/ring_attention.py), sequence-parallel
    activation sharding between blocks.
  * ``lax.scan`` over stacked layer params — one compilation regardless
    of depth; optional ``jax.checkpoint`` rematerialisation that keeps
    what the step's plan found room for on the device (``remat_layer``,
    ``models/remat.py``) and recomputes the rest in the backward pass.
  * bf16 activations/params with f32 RMSNorm + softmax + Adam moments.
  * A model is a LAYER PATTERN (``TransformerConfig.layer_pattern``):
    runs of layers of one kind, each run one scanned stack with its own
    ``remat_layer``.  A kind is an attention module and an FFN module,
    each with its own parameters and its own code in its own file: a
    model pays for the kinds it names.  ``models/kinds.py`` lists them,
    name -> the record the kind's module ends in
    (``common.LayerKind``), and this file asks that table: it names no
    kind.  With one run ``params["layers"]`` is that stack's tree (as it always
    was); with several it is a tuple of them, in order.  An entry of the
    pattern may also be a PERIOD, ``((run, run, ...), repeats)``: the
    runs in turn, that many times over, as one scan over periods whose
    body is the runs' own scans (a 3 : 1 pattern at 48 layers is one
    compiled body, not 24).  Its tree is a tuple of its runs' stacks,
    each with the periods as a further leading axis.
  * A run may say more than its kind, ``"kind:option,option"``
    (``kinds.run_options``; the kind's record says which): a window, a
    count of heads, a rotary table, or ``writes=<slot>`` /
    ``reads=<slot>``: its last layer hands something to the runs after
    it / its layers read what an earlier run handed on.  What is handed
    on lives in a SHARED SLOT that ``run_stacks`` threads
    beside ``x``: computed once in the forward pass, kept for its
    readers, their cotangents summed into the writer.  A run that reads
    a slot no earlier run wrote is refused when the configuration is
    made.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple, Union

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import NamedSharding, PartitionSpec as P

from ray_tpu.models import remat
from ray_tpu.models.common import (LayerCall, RopeTable, model_norm,
                                   norm_start)
from ray_tpu.models.kinds import ATTENTION, FFN, run_options
from ray_tpu.models.moe import update_bias
from ray_tpu.ops.attention_mask import CAUSAL
from ray_tpu.util import tracing


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32_000
    d_model: int = 512
    n_layers: int = 4
    n_heads: int = 8
    d_ff: int = 1408
    max_seq_len: int = 2048
    rope_theta: float = 10_000.0
    dtype: Any = jnp.bfloat16
    #: Recompute each layer in the backward pass from its input and what
    #: of it the device has room to keep (``remat_layer``: planned when
    #: the step is traced; nothing to set).  False: keep everything.
    remat: bool = True
    #: Use ring attention over the "sp" mesh axis when its size > 1.
    context_parallel: bool = True
    #: K/V heads (query head h reads K/V head h // group); 0: n_heads.
    n_kv_heads: int = 0
    #: 0: d_model // n_heads.
    head_dim: int = 0
    #: RMSNorm over each head of q and k before RoPE (weights
    #: ``q_norm``/``k_norm`` [head_dim], shared by the heads).
    qk_norm: bool = False
    norm_eps: float = 1e-5
    #: >0 replaces the dense FFN with a mixture of this many experts of
    #: width ``d_ff`` (models/moe.py): ``moe_top_k`` per token,
    #: renormalised over the chosen ones if ``moe_norm_topk``.  Expert
    #: weights shard over the "ep" mesh axis.
    moe_experts: int = 0
    moe_top_k: int = 2
    moe_norm_topk: bool = True
    #: Weight of the router's load-balance auxiliary in the loss
    #: (``moe.balance_loss``, mean over the layers); 0 leaves it a
    #: counter.
    moe_aux_coeff: float = 0.01
    #: The step's metrics also carry every token's experts,
    #: ``moe_choices`` [n_layers, B, S, moe_top_k] int32, so that a
    #: reference can follow the routing it checks.
    moe_report_choices: bool = False
    #: The experts' form: ``"swiglu"`` (``silu(x w1) . x w3``, then
    #: ``w2``) or ``"relu2"`` (``relu(x w1)^2``, then ``w2``: no ``w3``);
    #: the shared expert's too.
    moe_act: str = "swiglu"
    #: >0: the routed experts work in a latent of this width, between a
    #: down projection (``w_down [d_model, latent]``) and an up one
    #: (``w_up``) of the held share's sum; the router and the shared
    #: expert read the full width.
    moe_latent: int = 0
    #: ``(first, count)``: the experts this program holds of a layer
    #: shared with other chips (None: all).  The router stays
    #: ``moe_experts`` wide; what absent experts would add is left out.
    moe_experts_held: Optional[Tuple[int, int]] = None
    #: The experts' width; 0: ``d_ff`` (a mixed stack's dense layers
    #: and experts differ).
    moe_d_ff: int = 0
    #: ``"softmax"``, or ``"sigmoid"``: sigmoid scores.  Either way the
    #: gates are the chosen scores (renormalised if ``moe_norm_topk``)
    #: times ``moe_route_scale``.
    moe_scoring: str = "softmax"
    moe_route_scale: float = 1.0
    #: The sigmoid router's group step (DeepSeek-V3's ``noaux_tc``): the
    #: experts in ``moe_n_group`` equal groups, each scored by the sum of
    #: its two largest ``score + bias``, the choice within the
    #: ``moe_topk_group`` best groups.  1 and 1: no group step.
    moe_n_group: int = 1
    moe_topk_group: int = 1
    #: The share of blocks of tokens that route alike which the expert
    #: layer's first, always computed chunk may fall short of
    #: (``moe.chunk_rows``): the smaller, the rarer a step that runs
    #: further chunks, and the more rows every step computes.
    moe_alike_tail: float = 0.01
    #: >0: every expert layer adds a shared SwiGLU of this width.
    moe_shared_width: int = 0
    #: >0: the sigmoid router chooses by score plus a correction bias
    #: that the step moves by this much towards the experts it loaded
    #: least (``moe.update_bias``).  The bias is state beside the
    #: parameters, ``state["moe_bias"]`` [expert layers, moe_experts]
    #: float32: no gradient, no weight decay, no Adam moments.
    moe_bias_rate: float = 0.0
    #: The shared expert's output is multiplied by ``sigmoid(x . wsg)``
    #: (``wsg`` [d_model, 1]).
    moe_shared_gate: bool = False
    #: Latent attention's sizes (``models.mla.MLAConfig``) for the
    #: ``"mla"`` kind.
    mla: Any = None
    #: The delta layers' sizes (``models.gdn.GDNConfig``) for the
    #: ``"gdn"`` kind.
    gdn: Any = None
    #: Kimi Delta Attention's sizes (``models.kda.KDAConfig``) for the
    #: ``"kda"`` kind.
    kda: Any = None
    #: Gated attention (the ``"mha"`` kind).  True: ``wq`` is twice as
    #: wide, query | gate a head, and the heads' output is multiplied
    #: elementwise by ``sigmoid(gate)`` before ``wo``.  ``"head"``: one
    #: scalar a head from a projection of its own (``wg [d_model,
    #: heads]``, arXiv:2505.06708's head-wise form), read from the
    #: layer's normed input as the elementwise one is.
    attn_out_gate: Any = False
    #: >0: RoPE turns the first ``rotary_dim`` columns of a head (the
    #: halves of that slice) and leaves the others as they are.
    rotary_dim: int = 0
    #: The model's RMSNorms (``ln1``, ``ln2``, ``ln_f``, ``q_norm``,
    #: ``k_norm``) scale by ``1 + w`` with ``w`` nought at the start.
    norm_plus_one: bool = False
    #: Entries ``(attention, ffn, count)``, a run of layers of one kind
    #: (names of ``models/kinds.py``), or ``((run, ...), repeats)``, a
    #: period of runs; ``n_layers`` is then their sum.  None:
    #: ``n_layers`` of the one kind the other fields describe.
    layer_pattern: Optional[Tuple[Any, ...]] = None
    #: Multi-token-prediction modules after the stack (0 or 1):
    #: ``models/mtp.py``, whose loss hooks in by ``loss_override``.
    mtp_depth: int = 0
    #: The state-space layers' sizes (``models.mamba.MambaConfig``) for
    #: the ``"mamba"`` and ``"gmu"`` kinds.
    mamba: Any = None
    #: The Mamba-2 mixers' sizes (``models.mamba2.Mamba2Config``) for the
    #: ``"mamba2"`` kind.
    mamba2: Any = None
    #: ``"rms"``, or ``"layernorm"``: the model's norms (``ln1``,
    #: ``ln2``, ``ln_f``) subtract the mean and have a bias beside the
    #: weight (``ln1_b``, ...); ``norm_eps`` is theirs.
    norm: str = "rms"
    #: ``"rotary"``, or ``"none"``: the ``"mha"`` kind turns nothing
    #: (``"diff"`` never does).
    rope: str = "rotary"
    #: The head is the embedding transposed: no ``lm_head`` leaf, and
    #: the embedding's gradient is the sum of both uses.
    tie_embeddings: bool = False
    #: The index of the pattern's first layer in the model it is a slice
    #: of (differential attention's ``lambda_init`` reads a layer's
    #: index).
    first_layer_index: int = 0
    #: The rotary tables an ``mha`` run may name (``rope=<name>``):
    #: ``((name, RopeTable), ...)`` or a dict.  A run that names none
    #: turns by ``rope_theta`` over ``rotary_dim``.
    rope_tables: Union[Dict[str, RopeTable],
                       Tuple[Tuple[str, RopeTable], ...]] = ()

    def __post_init__(self):
        if not self.n_kv_heads:
            object.__setattr__(self, "n_kv_heads", self.n_heads)
        if not self.head_dim:
            object.__setattr__(self, "head_dim",
                               self.d_model // self.n_heads)
        if self.n_heads % self.n_kv_heads:
            raise ValueError(f"{self.n_heads} query heads over "
                             f"{self.n_kv_heads} K/V heads")
        tables = self.rope_tables
        object.__setattr__(self, "rope_tables", tuple(
            sorted(tables.items()) if isinstance(tables, dict) else tables))
        if self.attn_out_gate not in (False, True, "head"):
            raise ValueError(f"attn_out_gate {self.attn_out_gate!r}")
        if self.attn_out_gate != "head":
            object.__setattr__(self, "attn_out_gate",
                               bool(self.attn_out_gate))
        if self.layer_pattern is None:
            object.__setattr__(self, "layer_pattern", ((
                "mha" if self.mla is None else "mla",
                "moe" if self.moe_experts > 0 else "dense",
                self.n_layers),))
        else:
            pattern = tuple(_checked_entry(e) for e in self.layer_pattern)
            object.__setattr__(self, "layer_pattern", pattern)
            object.__setattr__(self, "n_layers",
                               sum(count for _, _, count in runs_of(pattern)))
        for attention, ffn, _ in runs_of(self.layer_pattern):
            for kind in (ATTENTION[run_options(attention)[0]], FFN[ffn]):
                # (unset: None for a kind's sizes, 0 for a count)
                if kind.needs and not getattr(self, kind.needs):
                    what = kind.needs if getattr(self, kind.needs) == 0 \
                        else f"the {kind.needs} sizes"
                    raise ValueError(f"a \"{kind.name}\" layer needs {what}")
        if self.moe_act not in ("swiglu", "relu2"):
            raise ValueError(f"moe_act {self.moe_act!r}")
        if self.norm not in ("rms", "layernorm") or \
                self.rope not in ("rotary", "none"):
            raise ValueError(f"norm {self.norm!r}, rope {self.rope!r}")
        if self.norm == "layernorm" and self.norm_plus_one:
            raise ValueError("a LayerNorm's weight scales as it is")
        _check_slots(self.layer_pattern)
        _check_mesh(self, None)
        if (self.tie_embeddings or self.norm != "rms") and self.mtp_depth:
            raise ValueError("the multi-token-prediction module has its "
                             "own head and RMSNorms")
        if self.mtp_depth not in (0, 1):
            raise ValueError("one multi-token-prediction module at most")
        if self.mtp_depth and (is_period(self.layer_pattern[-1])
                               or self.norm_plus_one):
            raise ValueError("the multi-token-prediction module takes its "
                             "kind from a last entry that is a run, and "
                             "its norms scale by w")

    @property
    def moe_layers(self) -> int:
        """Expert layers, the multi-token-prediction module's included:
        the rows of the correction bias."""
        return sum(count for _, ffn, count in runs_of(self.layer_pattern)
                   if ffn == "moe") + self.mtp_depth


def is_period(entry) -> bool:
    """An entry of the layer pattern is a run ``(attention, ffn, count)``
    or a period ``((run, ...), repeats)``."""
    return len(entry) == 2


def _checked_entry(entry):
    if is_period(entry):
        runs, repeats = entry
        runs = tuple(_checked_entry(run) for run in runs)
        if repeats < 1 or not runs or any(is_period(run) for run in runs):
            raise ValueError(f"layer pattern period {entry!r}")
        return runs, repeats
    attention, ffn, count = entry
    if ffn not in FFN or count < 1:
        raise ValueError(f"layer pattern run {attention!r}, {ffn!r}, {count}")
    run_options(attention)
    return attention, ffn, count


def _check_mesh(cfg: TransformerConfig, mesh) -> None:
    """Every run's own check (``LayerKind.check``): of the configuration
    where it is made (``mesh`` None), and of the mesh where the state is
    laid out for one -- a run it cannot take is refused by its name."""
    for run, _, _ in runs_of(cfg.layer_pattern):
        kind, options = run_options(run)
        ATTENTION[kind].check(LayerCall(cfg, run, options, mesh=mesh))


def _check_slots(pattern) -> None:
    """Every slot is written by a run before the runs that read it; a
    period's runs neither write nor read (the slot would have to pass
    through its scan)."""
    written = set()
    for entry in pattern:
        for attention, _, _ in runs_of((entry,)):
            options = run_options(attention)[1]
            slot = options.get("reads") or options.get("writes")
            if slot and is_period(entry):
                raise ValueError(f"{attention!r} in a period: the shared "
                                 f"slot does not pass through its scan")
            if "reads" in options and slot not in written:
                raise ValueError(f"{attention!r} reads the slot {slot!r} "
                                 f"that no earlier run writes")
            if "writes" in options:
                written.add(slot)


def runs_of(pattern):
    """Every run of the pattern in the order the layers come, a period's
    runs once a repeat: ``(attention, ffn, count)``."""
    for entry in pattern:
        if is_period(entry):
            runs, repeats = entry
            for _ in range(repeats):
                yield from runs
        else:
            yield entry


def init_stack(key: jax.Array, cfg: TransformerConfig, attention: str,
               ffn: str, nl: int) -> Dict:
    """The stacked parameters of ``nl`` layers of one kind: the two
    norms, then what the run's kinds bring (each folds its own constant
    into the stack's key)."""
    d = cfg.d_model
    norms = ("ln1", "ln2") if FFN[ffn].apply else ("ln1",)
    layers: Dict = {name: norm_start(cfg, (nl, d)) for name in norms}
    if cfg.norm == "layernorm":
        layers.update({name + "_b": jnp.zeros((nl, d), jnp.float32)
                       for name in norms})
    kind, options = run_options(attention)
    layers.update(ATTENTION[kind].init(key, nl, cfg, options))
    layers.update(FFN[ffn].init(key, nl, cfg, {}))
    return layers


def init_params(rng: jax.Array, cfg: TransformerConfig) -> Dict:
    k_embed, k_layers, k_head = jax.random.split(rng, 3)
    d = cfg.d_model
    init = jax.nn.initializers.normal(0.02)
    pattern = cfg.layer_pattern
    if len(pattern) == 1 and not is_period(pattern[0]):
        layers = init_stack(k_layers, cfg, *pattern[0])
    else:
        layers = tuple(_init_entry(jax.random.fold_in(k_layers, 16 + i), cfg,
                                   entry) for i, entry in enumerate(pattern))
    params = {
        "embed": init(k_embed, (cfg.vocab_size, d), jnp.float32
                      ).astype(cfg.dtype),
        "layers": layers,
        "ln_f": norm_start(cfg, (d,)),
    }
    if cfg.norm == "layernorm":
        params["ln_f_b"] = jnp.zeros((d,), jnp.float32)
    if not cfg.tie_embeddings:
        params["lm_head"] = init(k_head, (d, cfg.vocab_size), jnp.float32
                                 ).astype(cfg.dtype)
    if cfg.mtp_depth:
        # the multi-token-prediction module (``models/mtp.py`` runs it):
        # two norms, a projection, one more layer of the pattern's LAST
        # kind -- a stack of one: it runs as the pattern's runs do -- and
        # a final norm of its own
        k_proj, k_layer = jax.random.split(jax.random.fold_in(rng, 3))
        params["mtp"] = {
            **{name: jnp.ones((d,), jnp.float32)
               for name in ("hnorm", "enorm", "ln_f")},
            "w_eh": init(k_proj, (2 * d, d), jnp.float32).astype(cfg.dtype),
            "layers": init_stack(k_layer, cfg, *pattern[-1][:2], 1)}
    return params


def _init_entry(key, cfg: TransformerConfig, entry):
    """A run's stack, or a period's: its runs' stacks with the periods
    as a further leading axis."""
    if not is_period(entry):
        return init_stack(key, cfg, *entry)
    runs, repeats = entry
    return tuple(jax.tree.map(
        lambda a, count=count: a.reshape(repeats, count, *a.shape[1:]),
        init_stack(jax.random.fold_in(key, i), cfg, attention, ffn,
                   repeats * count))
        for i, (attention, ffn, count) in enumerate(runs))


def stack_specs(cfg: TransformerConfig, attention: str, ffn: str) -> Dict:
    """PartitionSpecs of one kind's stack: the norms replicated, then
    the kinds' own (Megatron TP on heads and FFN-hidden; MoE expert
    weights over "ep")."""
    norms = ("ln1", "ln2") if FFN[ffn].apply else ("ln1",)
    layers: Dict = {name: P(None, None) for name in norms}
    if cfg.norm == "layernorm":
        layers.update({name + "_b": P(None, None) for name in norms})
    kind, options = run_options(attention)
    layers.update(ATTENTION[kind].specs(cfg, options))
    layers.update(FFN[ffn].specs(cfg, {}))
    return layers


def param_specs(cfg: TransformerConfig) -> Dict:
    """PartitionSpecs following the layer pattern; vocab on lm_head."""
    def entry_specs(entry):
        if not is_period(entry):
            return stack_specs(cfg, *entry[:2])
        # the periods are one more leading axis, not sharded
        return tuple(jax.tree.map(
            lambda spec: P(None, *spec), stack_specs(cfg, attention, ffn),
            is_leaf=lambda x: isinstance(x, P))
            for attention, ffn, _ in entry[0])

    stacks = tuple(entry_specs(entry) for entry in cfg.layer_pattern)
    specs = {
        "embed": P(None, "tp"),
        "layers": (stacks[0] if len(stacks) == 1
                   and not is_period(cfg.layer_pattern[0]) else stacks),
        "ln_f": P(None),
    }
    if cfg.norm == "layernorm":
        specs["ln_f_b"] = P(None)
    if not cfg.tie_embeddings:
        specs["lm_head"] = P(None, "tp")
    if cfg.mtp_depth:
        attention, ffn, _ = cfg.layer_pattern[-1]
        specs["mtp"] = {"hnorm": P(None), "enorm": P(None),
                        "w_eh": P(None, None), "ln_f": P(None),
                        "layers": stack_specs(cfg, attention, ffn)}
    return specs


def apply_layer(x, lp, positions, cfg: TransformerConfig, mesh=None,
                mask=CAUSAL, kind: Optional[Tuple[str, str]] = None,
                index=None, shared: Optional[Dict] = None):
    """One block on [B, S, D] activations with this layer's params
    ``lp`` -> (x, what the layer counted, what it hands to later layers
    or None).  ``kind``: the layer's (attention, ffn), by default the
    pattern's first run's; ``index``: the layer's index in the model;
    ``shared``: the slots written so far.  Every objective's scan and
    the pipeline-parallel stage executor run this one."""
    # The named scopes here and in loss_fn / train_step are metadata
    # only: stable names for a device trace to group time by.
    run, ffn = kind or next(runs_of(cfg.layer_pattern))[:2]
    attention, options = run_options(run)
    record = ATTENTION[attention]
    call = LayerCall(cfg, run, options, positions, mesh, mask, index, shared)
    with jax.named_scope("attention"), record.run_scope(options):
        h = model_norm(x, lp, "ln1", cfg)
        y, counted, handed_on = record.apply(h, lp, call)
        x = x + y
        if FFN[ffn].apply:           # (a layer may be its mixer alone:
            x = checkpoint_name(x, "mid_residual")    # x is its output)
    ffn_counted = {}
    if FFN[ffn].apply:
        with jax.named_scope("ffn"):
            h = model_norm(x, lp, "ln2", cfg)
            y, ffn_counted, _ = FFN[ffn].apply(h, lp, call)
            x = x + y
    if mesh is not None:
        x = jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, P("dp", "sp", None)))
    return x, {**counted, **ffn_counted}, handed_on


def remat_layer(layer, cfg: TransformerConfig,
                kind: Tuple[str, ...] = (), mesh=None):
    """``layer(carry, scanned)`` as a scan over the stacked layers runs
    it: under ``cfg.remat`` its backward pass recomputes the layer from
    its input, all but what its policy keeps (``models/remat.py``: the
    kernels' own residuals always; beyond them those of the layer's
    named cut points that the step's plan found room for on the device).
    ``kind`` and ``mesh``: what the plan calls the run, and the mesh its
    token axes are split over."""
    if not cfg.remat:
        return layer
    return jax.checkpoint(layer, policy=remat.policy(kind, mesh))


def _reads_index(runs) -> bool:
    """Whether a layer of ``runs`` reads its index in the model."""
    return any(ATTENTION[run_options(run[0])[0]].indexed for run in runs)


def run_stack(x, stack: Dict, kind, positions, cfg: TransformerConfig,
              mesh=None, mask=CAUSAL, moe_bias=None, first_index: int = 0,
              shared: Optional[Dict] = None):
    """The scan over one run's stacked layers -> (x, what each layer
    counted, stacked by layer).  ``moe_bias`` [layers of the run, E]:
    the expert layers' correction bias, a row a layer.  ``first_index``:
    the index of the run's first layer; ``shared``: the slots written so
    far, by name -- a run that reads one takes it from there (the scan
    closes over it: kept once, its cotangent summed over the run's
    layers), a run that writes one puts its LAST layer's there."""
    options = run_options(kind[0])[1]
    shared = {} if shared is None else shared
    reads = {slot: shared[slot] for slot in (options.get("reads"),) if slot}

    def layer(x, scanned):
        lp, bias, index = scanned
        if bias is not None:
            lp = dict(lp, moe=dict(lp["moe"], bias=bias))
        x, counted, handed_on = apply_layer(x, lp, positions, cfg, mesh,
                                            mask, kind, index, reads)
        return x, (counted, handed_on)

    # (only a kind that reads its index is given one: the others' scans
    # are traced as they always were)
    indices = None
    if _reads_index((kind,)):
        indices = first_index + jnp.arange(
            jax.tree.leaves(stack)[0].shape[0], dtype=jnp.int32)
    x, (counted, handed_on) = jax.lax.scan(
        remat_layer(layer, cfg, kind, mesh), x, (stack, moe_bias, indices))
    if "writes" in options:
        shared[options["writes"]] = jax.tree.map(lambda a: a[-1], handed_on)
    return x, counted


def run_period(x, stacks, runs, positions, cfg: TransformerConfig, mesh=None,
               mask=CAUSAL, moe_bias=None, first_index=0):
    """The scan over a period's repeats, its body the runs' own scans ->
    (x, what the layers counted, stacked by layer in the order they
    come).  ``stacks``: a stack a run, ``[repeats, count, ...]``;
    ``moe_bias`` [expert layers of the entry, E]."""
    repeats = jax.tree.leaves(stacks)[0].shape[0]
    if moe_bias is not None:
        moe_bias = moe_bias.reshape(repeats, -1, moe_bias.shape[-1])

    starts = None
    if _reads_index(runs):
        length = sum(count for _, _, count in runs)
        starts = first_index + length * jnp.arange(repeats, dtype=jnp.int32)

    def period(x, scanned):
        return run_stacks(x, scanned[0], positions, cfg, mesh, mask,
                          scanned[1], pattern=runs, first_index=scanned[2])

    x, counted = jax.lax.scan(period, x, (tuple(stacks), moe_bias, starts))
    # [repeats, layers of a run that count it, ...] a run -> by layer
    by_layer = {}
    for name in sorted({name for c in counted for name in c}):
        v = jnp.concatenate([c[name] for c in counted if name in c], axis=1)
        by_layer[name] = v.reshape(-1, *v.shape[2:])
    return x, by_layer


def run_stacks(x, layers, positions, cfg: TransformerConfig, mesh=None,
               mask=CAUSAL, moe_bias=None, pattern=None, first_index=None):
    """Every entry of the layer pattern in turn (``pattern``: of a
    period's runs, with ``layers`` their stacks) -> (x, [what the layers
    of each entry counted, stacked by layer]).  The shared slot starts
    empty here and passes from run to run beside ``x``."""
    if pattern is None:
        # (``params["layers"]``: one run's stack is the tree itself)
        pattern = cfg.layer_pattern
        layers = (layers,) if isinstance(layers, dict) else tuple(layers)
    if first_index is None:
        first_index = cfg.first_layer_index
    counted, row, shared = [], 0, {}
    for stack, entry in zip(layers, pattern):
        bias = None
        rows = sum(count for _, ffn, count in runs_of((entry,))
                   if ffn == "moe")
        if moe_bias is not None and rows:
            bias, row = moe_bias[row:row + rows], row + rows
        if is_period(entry):
            x, c = run_period(x, stack, entry[0], positions, cfg, mesh, mask,
                              bias, first_index)
        else:
            x, c = run_stack(x, stack, entry[:2], positions, cfg, mesh, mask,
                             bias, first_index, shared)
        first_index = first_index + sum(
            count for _, _, count in runs_of((entry,)))
        counted.append(c)
    return x, counted


def reduce_counters(counted) -> Dict:
    """The runs' per-layer counters as a step reports them: scalars as
    means over the layers that count them, anything else stacked by
    layer (the runs in order)."""
    out = {}
    for name in sorted({name for c in counted for name in c}):
        runs = [c[name] for c in counted if name in c]
        v = runs[0] if len(runs) == 1 else jnp.concatenate(runs)
        out[name] = jnp.mean(v) if v.ndim == 1 else v
    return out


def embed_tokens(params: Dict, tokens: jax.Array, mesh=None):
    x = jnp.take(params["embed"], tokens, axis=0)     # [B, S, D]
    if mesh is not None:
        x = jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, P("dp", "sp", None)))
    return x


def run_layers(params: Dict, tokens: jax.Array, positions: jax.Array,
               cfg: TransformerConfig, mesh=None, mask=CAUSAL,
               moe_bias=None):
    """Embedding and the scans over the layer pattern's runs: tokens
    [B, S] -> (x [B, S, D] before the final norm, what the layers
    counted: ``reduce_counters``)."""
    x, counted = run_stacks(embed_tokens(params, tokens, mesh),
                            params["layers"], positions, cfg, mesh, mask,
                            moe_bias)
    return x, reduce_counters(counted)


def with_balance_loss(loss, counters: Dict, cfg: TransformerConfig):
    """The objective's loss plus the router's load-balance auxiliary,
    where the model has one."""
    if cfg.moe_experts > 0 and cfg.moe_aux_coeff > 0:
        loss = loss + cfg.moe_aux_coeff * counters["moe_balance_loss"]
    return loss


def forward(params: Dict, tokens: jax.Array, cfg: TransformerConfig,
            mesh=None) -> jax.Array:
    """tokens [B, S] int32 -> logits [B, S, V]."""
    return forward_with_counters(params, tokens, cfg, mesh)[0]


def forward_with_counters(params: Dict, tokens: jax.Array,
                          cfg: TransformerConfig, mesh=None, moe_bias=None):
    """Like :func:`forward` but also returns the expert layers'
    counters (nothing for dense models)."""
    B, S = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    x, counters = run_layers(params, tokens, positions, cfg, mesh,
                             moe_bias=moe_bias)
    with jax.named_scope("head_loss"):
        x = model_norm(x, params, "ln_f", cfg)
        if cfg.tie_embeddings:
            logits = jnp.einsum("bsd,vd->bsv", x, params["embed"])
        else:
            logits = jnp.einsum("bsd,dv->bsv", x, params["lm_head"])
    return logits, counters


def loss_and_counters(params: Dict, batch: Dict, cfg: TransformerConfig,
                      mesh=None, moe_bias=None):
    """Next-token cross entropy (plus the router's load-balance
    auxiliary) and the expert layers' counters.
    batch = {"tokens": [B, S+1] int32}."""
    tokens = batch["tokens"]
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    logits, counters = forward_with_counters(params, inputs, cfg, mesh,
                                             moe_bias)
    with jax.named_scope("head_loss"):
        logits = logits.astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, targets[..., None],
                                   axis=-1).squeeze(-1)
        loss = jnp.mean(logz - gold)
    return with_balance_loss(loss, counters, cfg), counters


def loss_fn(params: Dict, batch: Dict, cfg: TransformerConfig,
            mesh=None) -> jax.Array:
    return loss_and_counters(params, batch, cfg, mesh)[0]


# ---------------------------------------------------------------------------
# Train state + step factory (used by ray_tpu.train and the graft entry).
# ---------------------------------------------------------------------------

def make_train_state(rng, cfg: TransformerConfig, mesh=None,
                     learning_rate: float = 3e-4,
                     specs_override: Optional[Dict] = None):
    import optax
    tx = optax.adamw(learning_rate, b1=0.9, b2=0.95, weight_decay=0.1)
    params = init_params(rng, cfg)
    opt_state = tx.init(params)
    state = {"params": params, "opt": opt_state,
             "step": jnp.zeros((), jnp.int32)}
    if cfg.moe_bias_rate > 0:
        # beside the parameters and outside ``tx``: the step moves it by
        # rule (``moe.update_bias``)
        state["moe_bias"] = jnp.zeros((cfg.moe_layers, cfg.moe_experts),
                                      jnp.float32)
    if mesh is not None:
        _check_mesh(cfg, mesh)
        specs = specs_override or param_specs(cfg)
        # Adam moments mirror the param tree's specs.
        state_specs = {"params": specs, "step": P(),
                       "opt": _opt_specs(opt_state, specs)}
        if "moe_bias" in state:
            state_specs["moe_bias"] = P()
        state = jax.device_put(
            state, jax.tree.map(
                lambda s: NamedSharding(mesh, s), state_specs,
                is_leaf=lambda x: isinstance(x, P)))
    return state, tx


def _opt_specs(opt_state, param_spec_tree):
    """Mirror param specs onto the Adam moment trees, P() elsewhere."""
    def one(entry):
        if hasattr(entry, "mu") and hasattr(entry, "nu"):
            return type(entry)(count=P(), mu=param_spec_tree,
                               nu=param_spec_tree)
        return jax.tree.map(lambda _: P(), entry)
    return tuple(one(e) for e in opt_state)


def make_train_step(cfg: TransformerConfig, tx, mesh=None,
                    loss_override=None):
    """``loss_override(params, batch)`` substitutes the next-token loss
    (the pipeline-parallel schedule; the block-diffusion objective,
    ``models/block_diffusion.py``; the multi-token one,
    ``models/mtp.py``).  It returns the loss, or the loss and a dict of
    counters that join the step's ``metrics``.  Where the state holds
    the routers' correction bias (``moe_bias_rate``), the loss is
    called with it as a third argument, its counters carry every expert
    layer's loads (``moe_router_load``), and the step moves the bias by
    them.

    Under ``cfg.remat``, what the layer scans keep for the backward pass
    is planned where the step is traced (``remat.value_and_grad``)."""
    plans: Dict = {}      # the plans of this step's traces, by shapes
    kept: Dict = {}       # ... and the last one, as published

    def train_step(state, batch):
        compute = loss_override or (
            lambda p, b, *bias: loss_and_counters(p, b, cfg, mesh, *bias))
        bias = (state["moe_bias"],) if "moe_bias" in state else ()

        def objective(p):
            out = compute(p, batch, *bias)
            return out if isinstance(out, tuple) else (out, {})

        ((loss, counters), grads), plan = remat.value_and_grad(
            objective, state["params"], mesh, plans)
        kept.clear()
        kept.update(plan)
        with jax.named_scope("optimizer"):
            updates, new_opt = tx.update(grads, state["opt"],
                                         state["params"])
            new_params = jax.tree.map(
                lambda p, u: (p.astype(jnp.float32) + u).astype(p.dtype),
                state["params"], updates)
        new_state = {"params": new_params, "opt": new_opt,
                     "step": state["step"] + 1}
        if bias:
            with jax.named_scope("moe_bias"):
                counters = dict(counters)
                new_state["moe_bias"] = update_bias(
                    bias[0], counters.pop("moe_router_load"),
                    cfg.moe_bias_rate)
                counters["moe_bias_abs_max"] = jnp.max(
                    jnp.abs(new_state["moe_bias"]))
        metrics = {"loss": loss, "grad_norm": optax_global_norm(grads),
                   **counters}
        return new_state, metrics

    return _TracedStep(jax.jit(train_step, donate_argnums=(0,)), kept)


class _TracedStep:
    """The jitted ``train_step``, each call under a ``train.model_step``
    span: the host's dispatch of the step (argument flattening, cache
    lookup, donation, launch — it returns before the device finishes),
    i.e. the train worker's own cost per step.  Everything else of the
    jitted function (``.lower``, ``.trace``, ...) is reached through.

    The first call also offers the program to ``tracing.programs()`` as
    ``"train_step"``: the jitted function with the arguments' shapes,
    dtypes and shardings (the state is donated, so nothing concrete is
    kept).  Whoever reads that entry pays for its manifest, afterwards;
    the step pays one ``tree.map``.  The entry's ``memory`` carries,
    under ``"remat"``, what the step's layer scans keep for the backward
    pass (``models/remat.py``: the names a run, their bytes, the budget
    they were held to, the names refused for room, and ``plan_seconds``,
    what making the plan cost the trace), which is also on ``/metrics``
    as ``ray_tpu.train.remat_kept_bytes``, ``remat_budget_bytes`` and
    ``remat_plan_seconds`` once the step has been traced."""

    def __init__(self, jitted, kept=None):
        self._jitted = jitted
        self._kept = {} if kept is None else kept   # the plan, as published
        self._offered = False

    def __call__(self, state, batch):
        # (a call under a trace, ``jax.eval_shape(step, ...)``, has no
        # shardings to offer: the first concrete call registers)
        offer = not self._offered and not any(
            isinstance(x, jax.core.Tracer)
            for x in jax.tree.leaves((state, batch)))
        if offer:
            self._offered = True
            tracing.register_program(
                "train_step", self._jitted,
                *jax.tree.map(_abstract_argument, (state, batch)),
                memory=lambda: {"remat": dict(self._kept)})
        with tracing.span("train.model_step", category="train"):
            out = self._jitted(state, batch)
        if offer:
            from ray_tpu._private.metrics_agent import record_internal
            for name in ("kept_bytes", "budget_bytes", "plan_seconds"):
                record_internal(f"ray_tpu.train.remat_{name}",
                                float(self._kept.get(name) or 0))
        return out

    def __getattr__(self, name):
        return getattr(self._jitted, name)


def _abstract_argument(x):
    """An argument's leaf as ``lower`` takes it in the leaf's place: an
    uncommitted array (a ``jit``'s output) names no device, as in the
    call itself."""
    if not isinstance(x, jax.Array):
        return x
    return jax.ShapeDtypeStruct(
        x.shape, x.dtype, weak_type=x.weak_type,
        sharding=x.sharding if x.committed else None)


def optax_global_norm(tree) -> jax.Array:
    leaves = jax.tree.leaves(tree)
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                        for x in leaves))
