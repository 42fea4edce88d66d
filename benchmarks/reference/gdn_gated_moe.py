"""Plain reference of a training step of a hybrid decoder -- Gated DeltaNet
linear attention in three layers of four, gated softmax attention in the
fourth, sparse experts with a gated shared expert in all (Qwen3-Next's
layers, as ``transformers``' ``modeling_qwen3_next.py`` writes them) --
on one expert-parallel rank's share.  Straightforward ``jax.numpy`` in
float32 with every matrix multiplication at ``highest`` precision; no
kernels, no chunks, no sorting, no batching.  It imports nothing of the
program under test (the helpers it shares with the other references are
the benchmark's own).

For one row ``x [S, d]``, positions ``0..S-1``; every RMSNorm but the
delta layer's output norm scales by ``1 + w``:

  delta layer, ``h = rmsnorm(x; 1 + ln1)``, Hk key heads, Hv = r Hk value
  heads (key head j serves value heads r j .. r j + r - 1):
    q | k | v | z = h W_qkvz    [Dk | Dk | r Dv | r Dv] a key head
    b | a         = h W_ba      [r | r] a key head
    q | k | v     = silu(conv(q | k | v)): each channel over its own last
                    4 positions (four shifted products, zeros before the
                    row's start, no bias)
    q, k          = q / sqrt(|q|^2 + 1e-6) / sqrt(Dk), k / sqrt(|k|^2 + 1e-6)
    beta = sigmoid(b);  g = -exp(A_log) softplus(a + dt_bias)
    per value head, TOKEN BY TOKEN from S = 0 at the row's start:
        S <- exp(g_t) S;  S <- S + k_t (beta_t (v_t - S^T k_t))^T
        o_t = S^T q_t
    x = x + (rmsnorm(o; norm) silu(z), heads joined) W_o
  attention layer, ``h = rmsnorm(x; 1 + ln1)``:
    q | gate = h W_q (a head: 256 | 256);  k = h W_k;  v = h W_v
    q, k = rope(rmsnorm_head(.; 1 + q_norm / k_norm)) on columns 0-63
    (halves of the slice), query head j reads K/V head j // group,
    causal softmax at head_dim^-1/2, a head at a time
    x = x + ((softmax v) sigmoid(gate), heads joined) W_o
  experts (every layer), ``h = rmsnorm(x; 1 + ln2)``:
    p = softmax(h W_r);  S = the k largest;  g_e = p_e / sum_S p
    x = x + sum over HELD e in S of g_e SwiGLU_e(h)
          + sigmoid(h w_sg) SwiGLU_shared(h)
  loss = mean cross entropy + coeff * mean over layers of
         E sum_e f_e mean_t p_te     (f: the share of token-choices)

The recurrence is a ``lax.scan`` over positions (in blocks of 64 only so
that its backward keeps one state a block, not one a position: no
number changes).  What absent experts would add is left out, as in the
program; each held expert runs over every position with its gate.

Routing.  As ``block_diffusion_moe.follow``: handed the experts the
program chose (``choices``), it computes with those, its own
probabilities as gates, and holds every choice to its own probabilities:
``routing_gap`` is the mean over all tokens, layers and steps of
log(own k-th largest probability) - log(least probability among the
experts given).  The auxiliary loss takes its own probabilities and the
loads of the experts used.

The rule alone.  At the published initialisation (``A_log = log U(0,
16)``) all but a head in a hundred forget within a few positions, and a
gap of gradient norms does not see what a remembering head's state, or
its cotangent, is kept in.  So ``follow`` also runs the recurrence alone,
forward AND backward, on seeded inputs of the step's own shape whose
heads' decay rates span 1e-4 to 16 a position (``rule_probe_inputs``: q,
k, v and the output's cotangent rounded to the configuration's type):
``rule_probe`` is its output and, by ``jax.vjp`` of the recurrence, all
five gradients.  The driver takes ``jax.vjp`` of the program's rule on
the same inputs, and ``rule_gaps`` holds it to the recurrence by the
worst head's norm of the DIFFERENCE over the norm: ``gdn_rule_gap`` over
the output, ``gdn_rule_grad_gap`` over the five gradients.

Controls (``follow``'s keywords), each the same code with one thing
changed: ``precision="fp8"``; ``decay=False`` (g = 0: a state that
never forgets); ``state="bfloat16"`` (the state rounded to bfloat16
after every position); ``dstate="bfloat16"`` (the state's COTANGENT
rounded to bfloat16 at every position of the backward pass: what a
backward state pass that carried ``dS`` in bfloat16 would do);
``attn_gate=False`` (attention's output gate left out);
``rotary="all"`` (rotary on all 256 columns); ``shared_gate=False``
(the shared expert's gate left out).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.harness.weights import seed_key
from benchmarks.reference.block_diffusion_moe import _rope
from benchmarks.reference.dense_decoder import (_adamw, _diff_norm,
                                                _embed_grad, _mm, _rms_norm,
                                                _tree_add)
from benchmarks.reference.mla_moe_mtp import (_attend_causal, _leaf_table,
                                              _static, _swiglu)

_F32 = jnp.float32
_L2_EPS = 1e-6


def _norm1p(x, w, eps):
    return _rms_norm(x, 1.0 + w, eps)


def _shifted(x, n):
    """x [S, ...] moved ``n`` positions later, zeros before the start."""
    if n == 0:
        return x
    return jnp.concatenate([jnp.zeros_like(x[:n]), x[:-n]], axis=0)


def _rounded(x, dtype):
    """``x`` at ``dtype``'s precision, still float32 (by
    ``reduce_precision``: the chip's compiler drops a float32 ->
    bfloat16 -> float32 pair of converts as excess precision)."""
    kind = jnp.finfo(dtype)
    return jax.lax.reduce_precision(x, kind.nexp, kind.nmant)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _cotangent_rounded(x, dtype):
    """``x`` itself; its cotangent is rounded to ``dtype`` on the way
    back."""
    return x


_cotangent_rounded.defvjp(
    lambda x, dtype: (x, None),
    lambda dtype, _, ct: (_rounded(ct, dtype),))


def delta_rule(q, k, v, g, beta, state_dtype=None, dstate_dtype=None):
    """q, k [S, H, Dk], v [S, H, Dv], g, beta [S, H] -> o [S, H, Dv]:
    the recurrence, a position at a time, elementwise in float32 (no
    matrix unit, so no precision to choose).  The controls':
    ``state_dtype``, the state rounded to it after every position;
    ``dstate_dtype``, the state's cotangent rounded to it at every
    position of the backward pass."""
    def step(s, x):
        q, k, v, g, beta = x
        if dstate_dtype is not None:
            s = _cotangent_rounded(s, dstate_dtype)
        s = jnp.exp(g)[:, None, None] * s
        read = jnp.sum(s * k[:, :, None], axis=1)                # S^T k
        s = s + k[:, :, None] * (beta[:, None] * (v - read))[:, None, :]
        o = jnp.sum(s * q[:, :, None], axis=1)                   # S^T q
        if state_dtype is not None:
            s = _rounded(s, state_dtype)
        return s, o

    length = q.shape[0]
    block = math.gcd(length, 64)
    blocks = tuple(a.reshape(length // block, block, *a.shape[1:])
                   for a in (q, k, v, g, beta))
    zero = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), _F32)
    _, o = jax.lax.scan(
        jax.checkpoint(lambda s, xs: jax.lax.scan(step, s, xs)), zero, blocks)
    return o.reshape(length, *o.shape[2:])


#: What a probe holds, in ``rule_probe``'s order: the rule's output and
#: the gradients of ``sum(o * do)`` by its five inputs.
PROBE_PARTS = ("o", "dq", "dk", "dv", "dg", "dbeta")


@functools.partial(jax.jit, static_argnames=("shape", "dtype"))
def _probe_draw(key, shape, dtype):
    rows, length, hv, dk, dv = shape
    kq, kk, kv, kg, kb, ko = jax.random.split(key, 6)

    def unit(x):
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    def normal(key, *tail):
        return jax.random.normal(key, (rows, length, hv) + tail, _F32)

    rate = jnp.exp(jnp.linspace(math.log(1e-4), math.log(16.0), hv))
    q, k, v, do = (x.astype(dtype).astype(_F32) for x in (
        unit(normal(kq, dk)) * dk ** -0.5, unit(normal(kk, dk)),
        normal(kv, dv), normal(ko, dv)))
    return (q, k, v, -rate * jax.nn.softplus(normal(kg) + 1.0),
            jax.nn.sigmoid(normal(kb)), do)


def rule_probe_inputs(seed: int, cfg: dict, rows: int, length: int):
    """(q, k, v, g, beta, do) for ``rows`` rows of ``length`` positions
    and the configuration's value heads, ``[rows, length, H, ...]``
    float32, drawn on the device from the seed: unit q and k (q times
    Dk^-1/2), v and the output's cotangent ``do`` ~ N(0, 1), beta =
    sigmoid(N(0, 1)), g = -rate softplus(N(0, 1) + 1) with the heads'
    rates spaced evenly in the logarithm from 1e-4 (a head that
    remembers the whole row) to 16 (the published range's end).  q, k, v
    and do are rounded to the configuration's type: what both sides are
    handed."""
    key = jax.random.fold_in(seed_key(seed),
                             int.from_bytes(b"rule", "little"))
    return _probe_draw(
        key, (rows, length, cfg["linear_num_value_heads"],
              cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]),
        jnp.dtype(cfg["dtype"]))


@functools.partial(jax.jit, static_argnames=("decay", "state", "dstate"))
def _rule_probe_row(q, k, v, g, beta, do, decay, state, dstate):
    def rule(q, k, v, g, beta):
        return delta_rule(q, k, v, g if decay else jnp.zeros_like(g), beta,
                          jnp.bfloat16 if state == "bfloat16" else None,
                          jnp.bfloat16 if dstate == "bfloat16" else None)

    o, vjp = jax.vjp(rule, q, k, v, g, beta)
    return (o, *vjp(do))


def rule_probe(inputs, decay: bool = True, state: str = "float32",
               dstate: str = "float32") -> dict:
    """The recurrence and its ``jax.vjp`` on a probe's ``inputs``, a row
    at a time -> ``PROBE_PARTS`` as float32 arrays ``[rows, S, H, ...]``
    on the host."""
    rows = [_rule_probe_row(*(x[r] for x in inputs), decay, state, dstate)
            for r in range(inputs[0].shape[0])]
    return {name: np.stack([np.asarray(row[i]) for row in rows])
            for i, name in enumerate(PROBE_PARTS)}


def _head_gaps(prog, ref):
    """``|prog - ref| / |ref|`` a head over ``[rows, S, H, ...]``."""
    prog = np.asarray(prog).astype(np.float32).reshape(*prog.shape[:3], -1)
    ref = np.asarray(ref, np.float32).reshape(*ref.shape[:3], -1)
    gaps = []
    for h in range(ref.shape[2]):
        a, b = prog[:, :, h].astype(np.float64), ref[:, :, h].astype(
            np.float64)
        gap = math.sqrt(float(np.sum((a - b) ** 2))
                        / max(float(np.sum(b ** 2)), 1e-300))
        gaps.append(gap if math.isfinite(gap) else math.inf)
    return gaps


def rule_gaps(prog: dict, ref: dict) -> dict:
    """Two probes (``PROBE_PARTS``) -> ``gdn_rule_gap``, the worst
    head's ``|prog - ref| / |ref|`` of the output, and
    ``gdn_rule_grad_gap``, the worst head's of the five gradients, each
    (gap, which part and head)."""
    worst = {}
    for name in PROBE_PARTS:
        if prog[name].shape != ref[name].shape:
            worst[name] = (math.inf, f"{name}: {prog[name].shape} against "
                           f"{ref[name].shape}")
            continue
        gaps = _head_gaps(prog[name], ref[name])
        at = int(np.argmax(gaps))
        worst[name] = (gaps[at], f"{name}, head {at} of {len(gaps)}")
    return {"gdn_rule_gap": worst["o"],
            "gdn_rule_grad_gap": max((worst[name] for name in
                                      PROBE_PARTS[1:]), key=lambda w: w[0])}


def _delta_mixer(lp, h, hp, precision):
    dk, dv, r, eps = hp["dk"], hp["dv"], hp["ratio"], hp["eps"]
    s = h.shape[0]
    qkvz = _mm(precision, "sd,dhc->shc", h, lp["gdn.w_qkvz"])
    ba = _mm(precision, "sd,dhc->shc", h, lp["gdn.w_ba"])
    mixed, z = qkvz[..., :2 * dk + r * dv], qkvz[..., 2 * dk + r * dv:]
    taps = lp["gdn.conv"]                                   # [Hk, C, K]
    n_taps = taps.shape[-1]
    mixed = jax.nn.silu(sum(_shifted(mixed, n_taps - 1 - j) * taps[..., j]
                            for j in range(n_taps)))

    def unit(x):
        return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True)
                                 + _L2_EPS)

    q = jnp.repeat(unit(mixed[..., :dk]) * dk ** -0.5, r, axis=1)
    k = jnp.repeat(unit(mixed[..., dk:2 * dk]), r, axis=1)
    v = mixed[..., 2 * dk:].reshape(s, -1, dv)
    beta = jax.nn.sigmoid(ba[..., :r]).reshape(s, -1)
    g = (-jnp.exp(lp["gdn.A_log"]) * jax.nn.softplus(
        ba[..., r:] + lp["gdn.dt_bias"])).reshape(s, -1)
    if not hp["decay"]:
        g = jnp.zeros_like(g)
    o = delta_rule(q, k, v, g, beta,
                   jnp.bfloat16 if hp["state"] == "bfloat16" else None,
                   jnp.bfloat16 if hp["dstate"] == "bfloat16" else None)
    o = _rms_norm(o, lp["gdn.norm"], eps) * jax.nn.silu(
        z.reshape(s, -1, dv))
    return _mm(precision, "shk,hkd->sd", o.reshape(s, -1, r * dv),
               lp["gdn.wo"])


def _attention_mixer(lp, h, hp, precision):
    eps, dh = hp["eps"], hp["head_dim"]
    positions = jnp.arange(h.shape[0])
    q = _mm(precision, "sd,dhk->shk", h, lp["wq"])
    k = _mm(precision, "sd,dhk->shk", h, lp["wk"])
    v = _mm(precision, "sd,dhk->shk", h, lp["wv"])
    q, gate = q[..., :dh], q[..., dh:]
    rotary = dh if hp["rotary"] == "all" else hp["rotary_dim"]

    def turned(x, w):
        x = _norm1p(x, w, eps)
        return jnp.concatenate(
            [_rope(x[..., :rotary], positions, hp["theta"]),
             x[..., rotary:]], axis=-1)

    q, k = turned(q, lp["q_norm"]), turned(k, lp["k_norm"])
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    o = _attend_causal(q, k, v, dh ** -0.5, precision)
    if hp["attn_gate"]:
        o = o * jax.nn.sigmoid(gate)
    return _mm(precision, "shk,hkd->sd", o, lp["wo"])


def _experts(lp, h, hp, precision, given, share):
    """The expert layer on ``h [S, d]`` -> (y, this row's part of the
    auxiliary loss given every expert's share ``share [E]`` of the
    step's token-choices, (the experts used [S, k], this row's routing
    gap as (mean, largest), the row's summed probabilities [E]))."""
    top_k, first = hp["top_k"], hp["first"]
    probs = jax.nn.softmax(_mm(precision, "sd,de->se", h, lp["moe.wr"]),
                           axis=-1)
    own, chosen = jax.lax.top_k(probs, top_k)                  # [S, k]
    gate, gap = own, jnp.zeros((2,), _F32)
    if given is not None:
        chosen = given
        gate = jnp.take_along_axis(probs, chosen, axis=-1)
        in_order = jnp.sort(chosen, axis=-1)
        distinct = jnp.all(in_order[:, 1:] > in_order[:, :-1], axis=-1) & \
            (in_order[:, 0] >= 0) & (in_order[:, -1] < probs.shape[-1])
        short = jnp.where(distinct, jnp.log(own[:, -1])
                          - jnp.log(jnp.min(gate, axis=-1)), jnp.inf)
        gap = jax.lax.stop_gradient(
            jnp.stack([jnp.mean(short), jnp.max(short)]))
    if hp["norm_topk"]:
        gate = gate / jnp.sum(gate, axis=-1, keepdims=True)

    def one_expert(y, ew):
        e, w1, w3, w2 = ew
        g = jnp.sum(jnp.where(chosen == first + e, gate, 0.0), axis=-1)
        return y + g[:, None] * _swiglu(h, w1, w3, w2, precision), None

    held = lp["moe.w1"].shape[0]
    y, _ = jax.lax.scan(jax.checkpoint(one_expert), jnp.zeros_like(h),
                        (jnp.arange(held), lp["moe.w1"], lp["moe.w3"],
                         lp["moe.w2"]))
    shared = _swiglu(h, lp["moe.ws1"], lp["moe.ws3"], lp["moe.ws2"],
                     precision)
    if hp["shared_gate"]:
        shared = shared * jax.nn.sigmoid(
            _mm(precision, "sd,do->so", h, lp["moe.wsg"]))
    total = jnp.sum(probs, axis=0)                             # [E]
    aux = hp["aux_scale"] * probs.shape[-1] * jnp.sum(share * total)
    return y + shared, aux, (chosen, gap, jax.lax.stop_gradient(total))


def layer(lp: dict, x, hp: dict, precision: str, given=None, share=None):
    """One block on one row ``x [S, d]`` -> ((x, the row's part of the
    auxiliary loss), (experts used, routing gap, summed probabilities));
    the kind is read off the layer's leaves."""
    h = _norm1p(x, lp["ln1"], hp["eps"])
    mixer = _delta_mixer if "gdn.w_qkvz" in lp else _attention_mixer
    x = x + mixer(lp, h, hp, precision)
    if share is None:
        share = jnp.zeros((lp["moe.wr"].shape[-1],), _F32)
    y, aux, routed = _experts(lp, _norm1p(x, lp["ln2"], hp["eps"]), hp,
                              precision, given, share)
    return (x + y, aux), routed


def head_loss(hp_: dict, x, targets, eps, n_tokens, precision):
    logits = _mm(precision, "sd,dv->sv", _norm1p(x, hp_["ln_f"], eps),
                 hp_["lm_head"])
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
    return jnp.sum(logz - gold) / n_tokens


@functools.partial(jax.jit, static_argnames=("hp", "precision"))
def _layer_fwd(lp, x, given, hp, precision):
    (x, _), routed = layer(lp, x, dict(hp), precision, given)
    return x, routed


@functools.partial(jax.jit, static_argnames=("hp", "precision"))
def _layer_bwd(lp, x, given, share, dy, hp, precision):
    """``given``: the experts the forward used (its own or the
    program's), so both passes route alike; ``share``: the step's load
    shares, which the auxiliary loss weighs the probabilities by."""
    _, vjp, _ = jax.vjp(
        lambda p, a: layer(p, a, dict(hp), precision, given, share),
        lp, x, has_aux=True)
    return vjp((dy, jnp.ones((), _F32)))                # (d lp, d x)


@functools.partial(jax.jit,
                   static_argnames=("eps", "n_tokens", "precision"))
def _head_vg(hp_, x, targets, eps, n_tokens, precision):
    return jax.value_and_grad(
        lambda p, a: head_loss(p, a, targets, eps, n_tokens, precision),
        argnums=(0, 1))(hp_, x)


def _flat(tree: dict, at: tuple) -> dict:
    """Layer ``at = (period, layer of the run)`` of a period's stack as
    a flat dict of float32 leaves (``gdn.w_qkvz``, ``moe.w1`` ...)."""
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        out[".".join(str(k.key) for k in path)] = leaf[at].astype(_F32)
    return out


def _groups(weights: dict):
    """The seed's tree -> (float32 update groups, the table's rows, the
    layers' names in order).  A row of the table: (label, [(group,
    leaf), ...]) as the program's leaves reduce -- a period's stack by
    (period, layer of the run), flattened."""
    groups = {"embed": {"embed": weights["embed"].astype(_F32)},
              "head": {"ln_f": weights["ln_f"].astype(_F32),
                       "lm_head": weights["lm_head"].astype(_F32)}}
    table = [("embed", [("embed", "embed")]), ("ln_f", [("head", "ln_f")]),
             ("lm_head", [("head", "lm_head")])]
    (runs,) = weights["layers"]
    periods = jax.tree.leaves(runs[0])[0].shape[0]
    counts = [jax.tree.leaves(run)[0].shape[1] for run in runs]
    order, members = [], [[] for _ in runs]
    for p in range(periods):
        for i, (run, count) in enumerate(zip(runs, counts)):
            for j in range(count):
                name = f"layer{len(order)}"
                groups[name] = _flat(run, (p, j))
                order.append(name)
                members[i].append(name)
    for i, names in enumerate(members):
        table += [(f"layers.0.{i}.{leaf}", [(n, leaf) for n in names])
                  for leaf in groups[names[0]]]
    return groups, table, order


def follow(make_weights, batches, cfg: dict, steps: int = 2,
           precision: str = "float32", learning_rate=None, choices=None,
           decay: bool = True, state: str = "float32",
           dstate: str = "float32", attn_gate: bool = True,
           rotary: str = "partial", shared_gate: bool = True,
           probe_seed: int = 0) -> dict:
    """Train ``steps`` steps from the seed's weights.  ``batches[t]`` is
    ``[rows, S + 1]`` int tokens.  Returns each step's loss (and its
    cross entropy and auxiliary parts), the first gradient's norm per
    leaf and the norm per leaf of the parameters' change over the steps,
    labelled as the program's tree flattens; ``choices`` (per step
    ``[layers, rows, S, k]``, the experts used), ``routing_gap`` (value,
    note) and ``rule_probe`` (the recurrence alone with its ``jax.vjp`` on
    the seed's probe at the step's shape: ``PROBE_PARTS``).  ``choices`` in: the program's, to be followed.
    The other keywords are the controls'."""
    o = cfg["optimizer"]
    lr = o["learning_rate"] if learning_rate is None else learning_rate
    opt = (lr, o["b1"], o["b2"], o["eps"], o["weight_decay"])
    eps = cfg["rms_norm_eps"]
    n_layers, n_experts = cfg["num_hidden_layers"], cfg["num_experts"]
    top_k = cfg["num_experts_per_tok"]
    coeff = cfg["router_aux_loss_coef"]

    p, table, order = _groups(make_weights())
    m = jax.tree.map(jnp.zeros_like, p)
    v = jax.tree.map(jnp.zeros_like, p)
    losses, parts, grad1, used, gaps, worst = [], [], None, [], [], (0.0, "")

    for t in range(1, steps + 1):
        tokens = jnp.asarray(batches[t - 1], jnp.int32)
        inputs, targets = tokens[:, :-1], tokens[:, 1:]
        n_rows, length = inputs.shape
        rows = range(n_rows)
        n_tokens = n_rows * length
        hp = _static({
            "eps": eps, "theta": float(cfg["rope_theta"]),
            "head_dim": cfg["head_dim"],
            "rotary_dim": int(cfg["head_dim"] * cfg["partial_rotary_factor"]),
            "dk": cfg["linear_key_head_dim"],
            "dv": cfg["linear_value_head_dim"],
            "ratio": (cfg["linear_num_value_heads"]
                      // cfg["linear_num_key_heads"]),
            "top_k": top_k, "norm_topk": bool(cfg["norm_topk_prob"]),
            "first": cfg["experts_held_first"],
            "aux_scale": coeff / (n_layers * n_tokens),
            "decay": decay, "state": state, "dstate": dstate,
            "attn_gate": attn_gate, "rotary": rotary,
            "shared_gate": shared_gate})

        xs = [[p["embed"]["embed"][inputs[r]] for r in rows]]
        step_used, shares, aux = [], [], 0.0
        for i, name in enumerate(order):
            outs = jax.block_until_ready(
                [_layer_fwd(p[name], x,
                            None if choices is None else
                            jnp.asarray(choices[t - 1][i][r], jnp.int32),
                            hp, precision)
                 for r, x in zip(rows, xs[-1])])
            xs.append([x for x, _ in outs])
            chosen = np.stack([np.asarray(c) for _, (c, _, _) in outs])
            step_used.append(chosen)
            load = np.bincount(chosen.reshape(-1).clip(0, n_experts - 1),
                               minlength=n_experts)
            shares.append(jnp.asarray(load / (n_tokens * top_k), _F32))
            total = sum(np.asarray(s, np.float64) for _, (_, _, s) in outs)
            aux += coeff / n_layers * n_experts * float(
                np.sum(np.asarray(shares[-1], np.float64) * total)
            ) / n_tokens
            for r, (_, (_, gap, _)) in zip(rows, outs):
                gaps.append(float(gap[0]))
                if float(gap[1]) > worst[0]:
                    worst = (float(gap[1]), f"step {t} layer {i} row {r}")
        used.append(np.stack(step_used))

        loss, g_head, dxs = 0.0, None, []
        for r in rows:
            l_r, (g_r, dx_r) = _head_vg(p["head"], xs[-1][r], targets[r],
                                        eps, n_tokens, precision)
            loss = loss + l_r
            g_head = g_r if g_head is None else _tree_add(g_head, g_r)
            dxs.append(dx_r)
        xs.pop()
        losses.append(float(loss) + aux)
        parts.append((float(loss), aux))

        norms = {}

        def update(name, g):
            p[name], m[name], v[name], norms[name] = _adamw(
                p[name], m[name], v[name], g, float(t), opt)

        update("head", g_head)
        for i in reversed(range(n_layers)):
            name, g_layer, x_in = order[i], None, xs.pop()
            for r in rows:
                g_r, dxs[r] = _layer_bwd(
                    p[name], x_in[r], jnp.asarray(step_used[i][r]),
                    shares[i], dxs[r], hp, precision)
                g_layer = g_r if g_layer is None else _tree_add(g_layer, g_r)
            update(name, g_layer)
            jax.block_until_ready(dxs)
        update("embed", {"embed": _embed_grad(
            inputs, jnp.stack(dxs), p["embed"]["embed"])})
        if t == 1:
            grad1 = _leaf_table(norms, table)

    del m, v
    start = _groups(make_weights())[0]
    change = {g: {k: _diff_norm(p[g][k], start[g][k]) for k in p[g]}
              for g in p}
    return {"losses": losses, "loss_parts": parts, "grad1_norm": grad1,
            "change_norm": _leaf_table(change, table), "choices": used,
            "rule_probe": rule_probe(
                rule_probe_inputs(probe_seed, cfg, n_rows, length), decay,
                state, dstate),
            "routing_gap": (float(np.mean(gaps)), "largest single token "
                            f"{worst[0]:.4g} at {worst[1]}")}
