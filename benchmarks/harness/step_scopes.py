"""Device milliseconds a step by the program's own layers: the join of
the traced window's device operations (``trace_reduce.op_seconds``) with
the manifest the program publishes of the step it ran
(``ray_tpu.util.tracing.device_time_by_scope``: which ``named_scope`` and
which pass owns each instruction; the rules live there, not here).  The
readers of ``benchmarks/layer_metrics/`` that sum a group of scopes call
this module; it computes once a run and writes the whole table, with
what the manifest cost, as one line on standard error.

A program that publishes no ``"train_step"`` (a raylet run, a program
from before the registry): every function returns None.
"""

from __future__ import annotations

import json
import sys

from benchmarks.harness import trace_reduce

#: metric -> the scopes and kernel events it sums (innermost scope wins
#: in the program's manifest, so ``attention`` is what lies outside the
#: ``mla_*``, ``gdn_*`` and kernel scopes, ``ffn`` what lies outside the
#: ``moe_*`` scopes, ``mtp_module`` what lies outside its layer's).
GROUPS = {
    "attn_proj_ms": ("attention", "mla_q", "mla_kv", "mla_out", "attn_gate"),
    "attn_kernels_ms": ("flash_attention_fwd", "flash_attention_bwd"),
    "delta_layers_ms": ("gdn_proj", "gdn_conv", "gdn_core", "gdn_out",
                        "gated_delta_fwd", "gated_delta_bwd"),
    "ffn_ms": ("ffn",),
    "experts_ms": ("moe_router", "moe_dispatch", "moe_experts",
                   "moe_combine", "moe_shared", "moe_bias"),
    "head_loss_ms": ("head_loss", "mtp_loss", "mtp_module",
                     "block_diffusion_loss"),
}
_KEY = "_step_scopes"


def by_scope_ms(ctx) -> dict | None:
    """{scope or None or "unknown": {phase: ms a step}} (``"unknown"``:
    one number) for the run's traced window, or None."""
    if _KEY not in ctx:
        ctx[_KEY] = _compute(ctx)
    return ctx[_KEY]


def _compute(ctx):
    from ray_tpu.util import tracing
    steps = ctx["facts"].get("steps")
    registry = getattr(tracing, "programs", None)
    entry = registry().get("train_step") if registry else None
    if not steps or entry is None:
        return None
    rows = ((name, seconds) for name, (_, seconds)
            in trace_reduce.op_seconds(ctx["trace"]).items())
    by_scope = tracing.device_time_by_scope(rows)
    if set(by_scope) == {"unknown"}:      # no event of this program
        return None
    per_step = 1e3 / steps / max(1, len(ctx["trace"]["device_ops"]))
    table = {
        scope: (value * per_step if scope == "unknown" else
                {phase: s * per_step for phase, s in value.items()})
        for scope, value in by_scope.items()}
    sys.stderr.write(json.dumps({"step_scopes": {
        "resolve_s": entry["resolve_s"], "text_bytes": entry["text_bytes"],
        "instructions": len(entry["scopes"]), "memory": entry["memory"],
        "ms_a_step": {str(k): v for k, v in table.items()}}}) + "\n")
    return table


def _total(phases) -> float:
    return phases if isinstance(phases, float) else sum(phases.values())


def group_ms(ctx, metric: str):
    """The milliseconds a step of ``GROUPS[metric]``'s scopes; None where
    the step has none of them."""
    table = by_scope_ms(ctx)
    if table is None:
        return None
    found = [table[s] for s in GROUPS[metric] if s in table]
    return sum(map(_total, found)) if found else None


def recompute_ms(ctx):
    """What ``jax.checkpoint`` computes a second time, every scope."""
    table = by_scope_ms(ctx)
    if table is None:
        return None
    return sum(v["recompute"] for k, v in table.items() if k != "unknown")


def attributed_pct(ctx):
    """The share of the window's device time that the manifest gives to
    a scope of the program or to one of its kernels."""
    table = by_scope_ms(ctx)
    if table is None:
        return None
    total = sum(map(_total, table.values()))
    if not total:
        return None
    named = sum(_total(v) for k, v in table.items()
                if k not in (None, "unknown"))
    return 100.0 * named / total
