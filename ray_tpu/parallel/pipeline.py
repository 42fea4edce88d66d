"""Pipeline parallelism: GPipe-schedule transformer training over a
``pp`` mesh axis.

TPU-first design (the scaling-book pipelining recipe): stages are
contiguous layer groups, the stacked layer params shard over ``pp`` on
their leading (layer) axis, and the whole schedule runs inside ONE
``shard_map`` — activations move stage-to-stage with ``lax.ppermute``
over ICI, microbatches keep every stage busy after the fill phase
(T = M + P - 1 steps for M microbatches over P stages), and the
backward pass is just jax AD through the shard_map (ppermute
transposes to the reverse rotation).  The reference framework has no
pipeline parallelism at all (SURVEY §5.7).

Scope: the first/last stages also own embedding / final-norm + head
(replicated params, used only where valid); the per-microbatch loss is
computed on the LAST stage and summed with ``psum``.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ray_tpu.models.common import _rms_norm, norm_weight
from ray_tpu.models.kinds import ATTENTION, run_options
from ray_tpu.models.transformer import (TransformerConfig, apply_layer,
                                        is_period, make_train_state,
                                        make_train_step, param_specs,
                                        remat_layer, runs_of)


def pp_param_specs(cfg: TransformerConfig) -> Dict:
    """Layer stacks shard over "pp" on the layer axis; embed/head/ln_f
    replicate (first/last stages read them)."""
    specs = param_specs(cfg)

    def shard_leading(spec):
        return P("pp", *spec[1:]) if len(spec) else spec

    specs["layers"] = jax.tree.map(
        shard_leading, specs["layers"],
        is_leaf=lambda s: isinstance(s, P))
    return specs


def make_pp_loss_fn(cfg: TransformerConfig, mesh, n_micro: int):
    """Returns loss(params, batch) running the GPipe schedule over the
    mesh's "pp" axis (optionally combined with a "dp" axis on the
    batch).  Requires n_layers % pp == 0 and (batch/dp) % n_micro == 0.
    """
    pp = mesh.shape["pp"]
    dp = mesh.shape.get("dp", 1)
    kinds = {run_options(run[0])[0] for run in runs_of(cfg.layer_pattern)}
    if any(ATTENTION[kind].single_device for kind in kinds) \
            or cfg.tie_embeddings or cfg.norm != "rms":
        raise ValueError(
            f"the pipeline schedule runs rotary attention stages with "
            f"RMSNorms and a head of their own: not {sorted(kinds)}, a tied "
            f"head or a LayerNorm (what a layer hands to later layers "
            f"would have to cross stages)")
    assert cfg.n_layers % pp == 0, "n_layers must divide over pp stages"
    # Composition limits of this schedule: the stage body runs
    # unsharded layer math, so head/FFN tensor parallelism and MoE
    # expert parallelism cannot ride the same shard_map (their
    # contractions would need in-body psums / ep constraints).
    assert mesh.shape.get("tp", 1) == 1, "pp does not compose with tp"
    assert mesh.shape.get("ep", 1) == 1, "pp does not compose with ep"
    assert len(cfg.layer_pattern) == 1 \
        and not is_period(cfg.layer_pattern[0]), \
        "a stage scans one kind of layer: no mixed layer pattern"
    assert cfg.moe_experts == 0, \
        "MoE composes with ep, not pp (its counters are not plumbed here)"
    rot = [(i, (i + 1) % pp) for i in range(pp)]

    def stage_loss(layers, embed, lnf, head, tokens):
        """Per-device body: ``layers`` is this stage's [L/pp, ...]
        slice; ``tokens`` this dp shard's [b, S+1]."""
        p = jax.lax.axis_index("pp")
        inputs, targets = tokens[:, :-1], tokens[:, 1:]
        b, S = inputs.shape
        assert b % n_micro == 0, "microbatches must divide the batch"
        mb = b // n_micro
        micro_in = inputs.reshape(n_micro, mb, S)
        micro_tgt = targets.reshape(n_micro, mb, S)
        positions = jnp.broadcast_to(
            jnp.arange(S, dtype=jnp.int32)[None], (mb, S))

        def run_stage(x):
            def body(h, lp):
                return apply_layer(h, lp, positions, cfg, mesh=None)[0], None

            return jax.lax.scan(
                remat_layer(body, cfg, cfg.layer_pattern[0][:2]), x,
                layers)[0]

        def ce(h, tgt):
            logits = jnp.einsum(
                "bsd,dv->bsv",
                _rms_norm(h, norm_weight(lnf, cfg), cfg.norm_eps),
                head).astype(jnp.float32)
            logz = jax.nn.logsumexp(logits, axis=-1)
            gold = jnp.take_along_axis(
                logits, tgt[..., None], axis=-1).squeeze(-1)
            return jnp.mean(logz - gold)

        state = jnp.zeros((mb, S, cfg.d_model), cfg.dtype)
        loss_sum = jnp.zeros((), jnp.float32)
        for t in range(n_micro + pp - 1):
            # Stage 0 injects microbatch t during the fill phase;
            # other stages consume what rotated in.
            inject = jnp.take(embed, micro_in[min(t, n_micro - 1)],
                              axis=0).astype(cfg.dtype)
            x = jnp.where((p == 0) & (t < n_micro), inject, state)
            y = run_stage(x)
            # The LAST stage finishes microbatch t - (pp - 1).
            m = t - (pp - 1)
            if 0 <= m < n_micro:
                loss_m = ce(y, micro_tgt[m])
                loss_sum = loss_sum + jnp.where(p == pp - 1, loss_m,
                                                0.0)
            state = jax.lax.ppermute(y, "pp", rot)
        # Loss lives on the last stage; psum shares it out.
        loss = jax.lax.psum(loss_sum, "pp") / n_micro
        if dp > 1:
            loss = jax.lax.pmean(loss, "dp")
        return loss

    in_specs = (
        pp_param_specs(cfg)["layers"],
        P(), P(), P(),                       # embed, ln_f, head
        P("dp", None) if dp > 1 else P(),    # tokens
    )
    smapped = jax.shard_map(
        stage_loss, mesh=mesh,
        in_specs=in_specs, out_specs=P(),
        check_vma=False)

    def loss_fn(params, batch):
        return smapped(params["layers"], params["embed"],
                       params["ln_f"], params["lm_head"],
                       batch["tokens"])

    return loss_fn


def make_pp_train_step(cfg: TransformerConfig, tx, mesh,
                       n_micro: int = 4):
    """Full pipeline-parallel train step: GPipe loss + AD through the
    shard_map (ppermute transposes to the reverse rotation) — the
    shared update rule/metrics come from the transformer factory."""
    pp_loss = make_pp_loss_fn(cfg, mesh, n_micro)
    return make_train_step(cfg, tx, mesh=mesh, loss_override=pp_loss)


def make_pp_train_state(rng, cfg: TransformerConfig, mesh,
                        learning_rate: float = 3e-4):
    """Train state placed with pp-sharded layer stacks (shared
    optimizer/placement logic; only the layer specs differ)."""
    specs = param_specs(cfg)
    specs["layers"] = pp_param_specs(cfg)["layers"]
    return make_train_state(rng, cfg, mesh=mesh,
                            learning_rate=learning_rate,
                            specs_override=specs)
