"""Plain reference of a training step of a decoder of Kimi Delta
Attention (KDA) and latent attention (MLA) layers over dense and expert
FFNs, as Ling-3.0 lays them out, written from its ``config.json`` as the
configuration file's ``assumed`` has it, on one expert-parallel rank's
share.  Straightforward ``jax.numpy`` in float32 with every matrix
multiplication at ``highest`` precision; no kernels, no chunks, no
sorting.  It imports nothing of the program under test (the helpers it
shares with the other references, and ``layer_plan``, are the
benchmark's own).

For one row ``x [S, d]``, positions ``0..S-1``, each layer
``x <- x + mixer(rmsnorm(x; ln1))``, then ``x <- x + ffn(rmsnorm(x;
ln2))`` (eps ``rms_norm_eps``):

  KDA (H heads of Dk = Dv, K taps):
    q | k | v = silu(sum_j qkv[t - (K - 1 - j)] conv_j),  qkv = h Wqkv
    q = l2norm(q) Dk^-1/2;  k = l2norm(k)                      (eps 1e-6)
    g = lower sigmoid(exp(A_log_h) (h W_alpha + dt_bias))   [H, Dk]
    beta = sigmoid(h W_beta)                                 [H]
    S <- Diag(exp(g_t)) S;  S <- S + beta_t k_t (v_t - S^T k_t)^T
    o_t = S^T q_t       float32, token by token (``kda_recurrence``)
    out = (rmsnorm(o; norm) sigmoid(h W_gate)) W_o
  MLA (no query latent):
    q = h W_q [S, H, dn + dr], each head RMS-normed over its dn + dr
    columns;  c_kv | k_r = h W_kv_a;  k_n | v = rmsnorm(c_kv) W_kv_b;
    k_n RMS-normed a head, k_r once;  rotary (halves, rope_theta) on
    q's last dr columns and on k_r;  causal softmax at (dn + dr)^-1/2
  dense: silu(h W1) (h W3) W2
  experts:
    s = sigmoid(h Wr) [E];  group score = sum of the two largest s + b
    in each of n_group groups;  e = the k largest s + b within the
    topk_group best groups;  gate_e = s_e / (sum over e + 1e-20) * scale
    out = sum over HELD e of gate_e swiglu_e(h) + swiglu_shared(h)
  loss = mean over the row of the next token's cross entropy, after the
  final rmsnorm and the untied head over the vocabulary slice

What absent experts would add is left out, as in the program; each held
expert runs over every position with its gate.  Layers run one by one,
forward then backward, a row at a time; both Adam moments wait on the
host between a group's updates: the chip holds the float32 weights and
one layer's working set.

Routing and the bias.  As ``mla_moe_mtp.follow``: handed the experts the
program chose (``choices``), it computes with those, its own scores as
gates, and holds every choice to its own ``s + b`` among the groups it
keeps itself, and every chosen expert's group to its own last kept
group (``routing_gap``); the bias moves after each step by the experts that step used, by
``router_bias_update_rate``.

The rule alone (``rule_probe``): the recurrence and its ``jax.vjp`` on a
seeded probe at the step's shape, whose channels' decays span the
gate's whole range, against which ``rule_gaps`` holds the program's
kernels.

Controls (``follow``'s keywords), each the same code with one thing
changed: ``precision="fp8"``; ``decay="head"`` (one decay a head: the
mean of ``g`` over its channels); ``state="bfloat16"`` (the state
rounded to bfloat16 after every position); ``groups=False`` (the
router's group step left out); ``gate="softplus"`` (``g = -exp(A_log)
softplus(h W_alpha + dt_bias)``, the unbounded Kimi Linear gate).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.harness.kda_weights import layer_plan
from benchmarks.harness.weights import seed_key
from benchmarks.reference.dense_decoder import (_adamw, _diff_norm,
                                                _embed_grad, _head_vg, _mm,
                                                _rms_norm, _rope, _tree_add)
from benchmarks.reference.gdn_gated_moe import (_head_gaps, _rounded,
                                                _shifted)
from benchmarks.reference.mla_moe_mtp import (_attend_causal, _groups,
                                              _leaf_table, _static, _swiglu)

_F32 = jnp.float32
_L2_EPS = 1e-6
_SCAN_BLOCK = 64


def kda_recurrence(q, k, v, g, beta, state_dtype=None):
    """q, k [S, H, Dk], v [S, H, Dv], g [S, H, Dk], beta [S, H] -> o [S,
    H, Dv]: every head's state token by token, elementwise in float32
    (rounded to ``state_dtype`` after every position where given); the
    positions in checkpointed blocks of 64, so the gradient keeps a
    state a block."""
    def step(s, x):
        q, k, v, g, beta = x
        s = jnp.exp(g)[:, :, None] * s
        read = jnp.sum(s * k[:, :, None], axis=1)                # S^T k
        s = s + k[:, :, None] * (beta[:, None] * (v - read))[:, None, :]
        if state_dtype is not None:
            s = _rounded(s, state_dtype)
        return s, jnp.sum(s * q[:, :, None], axis=1)             # S^T q

    length = q.shape[0]
    block = math.gcd(length, _SCAN_BLOCK)
    blocks = tuple(a.reshape(length // block, block, *a.shape[1:])
                   for a in (q, k, v, g, beta))
    zero = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), _F32)
    _, o = jax.lax.scan(
        jax.checkpoint(lambda s, xs: jax.lax.scan(step, s, xs)), zero, blocks)
    return o.reshape(length, *o.shape[2:])


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + _L2_EPS)


def _decays(lp, alpha, hp):
    """g [S, H, Dk] from the gate's projection, as ``hp`` says."""
    rate = jnp.exp(lp["kda.A_log"])[:, None]
    if hp["gate"] == "softplus":
        g = -rate * jax.nn.softplus(alpha + lp["kda.dt_bias"])
    else:
        g = hp["lower"] * jax.nn.sigmoid(rate * (alpha + lp["kda.dt_bias"]))
    if hp["decay"] == "head":
        g = jnp.broadcast_to(jnp.mean(g, axis=-1, keepdims=True), g.shape)
    return g


def _kda_mixer(lp, h, hp, precision):
    dk = hp["head_dim"]
    qkv = _mm(precision, "sd,dhc->shc", h, lp["kda.w_qkv"])
    taps = lp["kda.conv"]                                   # [H, 3 Dk, K]
    n = taps.shape[-1]
    mixed = jax.nn.silu(sum(_shifted(qkv, n - 1 - j) * taps[..., j]
                            for j in range(n)))
    q = _l2norm(mixed[..., :dk]) * dk ** -0.5
    k = _l2norm(mixed[..., dk:2 * dk])
    v = mixed[..., 2 * dk:]
    g = _decays(lp, _mm(precision, "sd,dhc->shc", h, lp["kda.w_alpha"]), hp)
    beta = jax.nn.sigmoid(_mm(precision, "sd,dh->sh", h, lp["kda.w_beta"]))
    o = kda_recurrence(q, k, v, g, beta, jnp.bfloat16
                       if hp["state"] == "bfloat16" else None)
    o = _rms_norm(o, lp["kda.norm"], hp["eps"]) * jax.nn.sigmoid(
        _mm(precision, "sd,dh->sh", h, lp["kda.w_gate"]))[..., None]
    return _mm(precision, "shk,hkd->sd", o, lp["kda.wo"])


def _latent_attention(lp, h, hp, precision):
    dn, rkv, eps, theta = hp["qk_nope"], hp["kv_rank"], hp["eps"], hp["theta"]
    q = _rms_norm(_mm(precision, "sd,dhk->shk", h, lp["mla.wq"]),
                  lp["mla.q_head_norm"], eps)
    latent = _mm(precision, "sd,dr->sr", h, lp["mla.wkv_a"])
    kv = _mm(precision, "sr,rhk->shk",
             _rms_norm(latent[:, :rkv], lp["mla.kv_norm"], eps),
             lp["mla.wkv_b"])
    k_n = _rms_norm(kv[..., :dn], lp["mla.k_head_norm"][:dn], eps)
    k_r = _rope(_rms_norm(latent[:, None, rkv:], lp["mla.k_head_norm"][dn:],
                          eps), theta)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], theta)], axis=-1)
    k = jnp.concatenate(
        [k_n, jnp.broadcast_to(k_r, k_n.shape[:2] + k_r.shape[2:])], axis=-1)
    a = _attend_causal(q, k, kv[..., dn:], q.shape[-1] ** -0.5, precision)
    return _mm(precision, "shk,hkd->sd", a, lp["mla.wo"])


def _groups_kept(select, hp):
    """-> (``select`` [S, E] with ``-inf`` on the experts of every group
    a token does not keep, each expert's group's score less the score of
    the last group kept [S, E]: < 0 outside the kept groups).  Without
    the group step (``hp["groups"]`` off, or one group): ``select`` and
    zeros."""
    if not hp["groups"] or hp["n_group"] == 1:
        return select, jnp.zeros_like(select)
    s, e = select.shape
    size = e // hp["n_group"]
    grouped = select.reshape(s, hp["n_group"], size)
    score = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)     # [S, G]
    last = jax.lax.top_k(score, hp["topk_group"])[0][:, -1:]
    above = jnp.repeat(score - last, size, axis=-1)            # [S, E]
    return jnp.where(above >= 0, select, -jnp.inf), above


def _experts(lp, h, bias, hp, precision, given):
    """The expert layer on ``h [S, d]`` -> (y, the experts used [S, k],
    this row's routing gap as (mean, largest)).  A token's gap is the
    larger of how far its least given expert's ``s + b`` lies under its
    own ``k``-th within its own kept groups, and how far the group of a
    given expert lies under its own last kept group."""
    top_k, first = hp["top_k"], hp["first"]
    score = jax.nn.sigmoid(_mm(precision, "sd,de->se", h, lp["moe.wr"]))
    select = score + jax.lax.stop_gradient(bias)
    kept, above = _groups_kept(select, hp)
    own, chosen = jax.lax.top_k(kept, top_k)                   # [S, k]
    gap = jnp.zeros((2,), _F32)
    if given is not None:
        chosen = given
        in_order = jnp.sort(chosen, axis=-1)
        distinct = jnp.all(in_order[:, 1:] > in_order[:, :-1], axis=-1) & \
            (in_order[:, 0] >= 0) & (in_order[:, -1] < score.shape[-1])
        least = jnp.min(jnp.take_along_axis(select, chosen, axis=-1), axis=-1)
        outside = -jnp.min(jnp.take_along_axis(above, chosen, axis=-1),
                           axis=-1)
        short = jnp.where(distinct, jnp.maximum(own[:, -1] - least, outside),
                          jnp.inf)
        gap = jax.lax.stop_gradient(
            jnp.stack([jnp.mean(short), jnp.max(short)]))
    gate = jnp.take_along_axis(score, chosen, axis=-1)
    gate = gate / (jnp.sum(gate, axis=-1, keepdims=True) + 1e-20) \
        * hp["route_scale"]

    def one_expert(y, ew):
        e, w1, w3, w2 = ew
        g = jnp.sum(jnp.where(chosen == first + e, gate, 0.0), axis=-1)
        return y + g[:, None] * _swiglu(h, w1, w3, w2, precision), None

    y, _ = jax.lax.scan(jax.checkpoint(one_expert), jnp.zeros_like(h),
                        (jnp.arange(lp["moe.w1"].shape[0]), lp["moe.w1"],
                         lp["moe.w3"], lp["moe.w2"]))
    y = y + _swiglu(h, lp["moe.ws1"], lp["moe.ws3"], lp["moe.ws2"],
                    precision)
    return y, chosen, gap


def layer(lp: dict, x, bias, hp: dict, precision: str, given=None):
    """One of the program's layers on one row ``x [S, d]`` -> (x,
    (experts used, routing gap))."""
    h = _rms_norm(x, lp["ln1"], hp["eps"])
    mixer = _kda_mixer if hp["mixer"] == "kda" else _latent_attention
    x = x + mixer(lp, h, hp, precision)
    h = _rms_norm(x, lp["ln2"], hp["eps"])
    if hp["ffn"] == "dense":
        return x + _swiglu(h, lp["w1"], lp["w3"], lp["w2"], precision), (
            jnp.zeros((x.shape[0], hp["top_k"]), jnp.int32),
            jnp.zeros((2,), _F32))
    y, chosen, gap = _experts(lp, h, bias, hp, precision, given)
    return x + y, (chosen, gap)


@functools.partial(jax.jit, static_argnames=("hp", "precision"))
def _layer_fwd(lp, x, bias, given, hp, precision):
    return layer(lp, x, bias, dict(hp), precision, given)


@functools.partial(jax.jit, static_argnames=("hp", "precision"))
def _layer_bwd(lp, x, bias, given, dy, hp, precision):
    """``given``: the experts the forward used (its own or the
    program's), so both passes route alike."""
    _, vjp, _ = jax.vjp(
        lambda p, a: layer(p, a, bias, dict(hp), precision, given),
        lp, x, has_aux=True)
    return vjp(dy)                                      # (d lp, d x)


# ---- the rule alone --------------------------------------------------------

#: What a probe holds: the rule's output and the gradients of ``sum(o *
#: do)`` by its five inputs.
PROBE_PARTS = ("o", "dq", "dk", "dv", "dg", "dbeta")


@functools.partial(jax.jit, static_argnames=("shape", "dtype", "lower"))
def _probe_draw(key, shape, dtype, lower):
    rows, length, h, dk, dv = shape
    kq, kk, kv, kg, kb, ko = jax.random.split(key, 6)

    def unit(x):
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    def normal(key, *tail):
        return jax.random.normal(key, (rows, length, h) + tail, _F32)

    # a channel's rate, 1e-4 (remembers the whole row) to the gate's
    # bound (forgets within a position), spaced evenly in the logarithm
    # and laid out across the channels in a different order a head
    rate = jnp.exp(jnp.linspace(math.log(1e-4), math.log(-lower), dk))
    rate = jax.vmap(lambda i: jnp.roll(rate, 37 * i))(jnp.arange(h))
    q, k, v, do = (x.astype(dtype).astype(_F32) for x in (
        unit(normal(kq, dk)) * dk ** -0.5, unit(normal(kk, dk)),
        normal(kv, dv), normal(ko, dv)))
    return (q, k, v, -rate * jax.nn.sigmoid(normal(kg, dk) + 2.0),
            jax.nn.sigmoid(normal(kb)), do)


def rule_probe_inputs(seed: int, cfg: dict, rows: int, length: int):
    """(q, k, v, g, beta, do) for ``rows`` rows of ``length`` positions
    at the configuration's heads, ``[rows, length, H, ...]`` float32,
    drawn on the device from the seed: unit q and k (q times Dk^-1/2), v
    and the output's cotangent ~ N(0, 1), beta = sigmoid(N(0, 1)), g =
    -rate sigmoid(N(0, 1) + 2) with the channels' rates spaced evenly in
    the logarithm from 1e-4 to the gate's bound (5), so g spans the
    gate's whole range.  q, k, v and do are rounded to the
    configuration's type: what both sides are handed."""
    key = jax.random.fold_in(seed_key(seed), int.from_bytes(b"kda", "little"))
    return _probe_draw(key, (rows, length, cfg["num_attention_heads"],
                             cfg["head_dim"], cfg["head_dim"]),
                       jnp.dtype(cfg["dtype"]), float(cfg["kda_lower_bound"]))


@functools.partial(jax.jit, static_argnames=("decay", "state"))
def _rule_probe_row(q, k, v, g, beta, do, decay, state):
    def rule(q, k, v, g, beta):
        if decay == "head":
            g = jnp.broadcast_to(jnp.mean(g, axis=-1, keepdims=True), g.shape)
        return kda_recurrence(q, k, v, g, beta, jnp.bfloat16
                              if state == "bfloat16" else None)

    o, vjp = jax.vjp(rule, q, k, v, g, beta)
    return (o, *vjp(do))


def rule_probe(inputs, decay: str = "channel",
               state: str = "float32") -> dict:
    """The recurrence and its ``jax.vjp`` on a probe's ``inputs``, a row
    at a time -> ``PROBE_PARTS`` as float32 arrays ``[rows, S, H, ...]``
    on the host."""
    rows = [_rule_probe_row(*(x[r] for x in inputs), decay, state)
            for r in range(inputs[0].shape[0])]
    return {name: np.stack([np.asarray(row[i]) for row in rows])
            for i, name in enumerate(PROBE_PARTS)}


def rule_gaps(prog: dict, ref: dict) -> dict:
    """Two probes (``PROBE_PARTS``) -> ``kda_rule_gap``, the worst
    head's ``|prog - ref| / |ref|`` of the output, and
    ``kda_rule_grad_gap``, the worst head's of the five gradients, each
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
    return {"kda_rule_gap": worst["o"],
            "kda_rule_grad_gap": max((worst[name] for name in
                                      PROBE_PARTS[1:]), key=lambda w: w[0])}


# ---- the steps -------------------------------------------------------------

def layer_hps(cfg: dict, decay: str = "channel", state: str = "float32",
              groups: bool = True, gate: str = "bounded") -> list:
    """What each layer of ``layer_plan`` is, as the static argument of
    its compiled functions; the keywords are the controls'."""
    return [_static({
        "mixer": entry["mixer"], "ffn": entry["ffn"],
        "eps": cfg["rms_norm_eps"], "head_dim": cfg["head_dim"],
        "lower": float(cfg["kda_lower_bound"]), "decay": decay,
        "state": state, "gate": gate,
        "qk_nope": cfg["qk_nope_head_dim"], "kv_rank": cfg["kv_lora_rank"],
        "theta": float(cfg["rope_theta"]),
        "top_k": cfg["num_experts_per_tok"], "groups": groups,
        "n_group": cfg["n_group"], "topk_group": cfg["topk_group"],
        "route_scale": float(cfg["routed_scaling_factor"]),
        "first": cfg["experts_held_first"]}) for entry in layer_plan(cfg)]


def follow(make_weights, batches, cfg: dict, steps: int = 2,
           precision: str = "float32", learning_rate=None, choices=None,
           **controls) -> dict:
    """Train ``steps`` steps from the seed's weights.  ``batches[t]`` is
    ``[rows, S + 1]`` int tokens.  Returns each step's loss, the first
    gradient's norm per leaf and the norm per leaf of the parameters'
    change over the steps, labelled as the program's tree flattens;
    ``choices`` (per step ``[expert layers, rows, S, k]``),
    ``routing_gap`` (value, note) and ``moe_bias`` ``[expert layers,
    E]`` after the steps.  ``choices`` in: the program's, to be
    followed.  ``controls``: ``layer_hps``' keywords."""
    o = cfg["optimizer"]
    lr = o["learning_rate"] if learning_rate is None else learning_rate
    opt = (lr, o["b1"], o["b2"], o["eps"], o["weight_decay"])
    eps = cfg["rms_norm_eps"]
    rate = np.float32(cfg["router_bias_update_rate"])
    n_experts = cfg["num_experts"]
    hps = layer_hps(cfg, **controls)

    p, table, order = _groups(make_weights())
    if len(order) != len(hps):
        raise ValueError(f"{len(order)} layers of weights for a plan of "
                         f"{len(hps)}")
    routed = [n for n in order if "moe.wr" in p[n]]
    bias = {n: jnp.zeros((n_experts,), _F32) for n in routed}
    none = jnp.zeros((n_experts,), _F32)
    m = jax.tree.map(lambda a: np.zeros(a.shape, np.float32), p)   # host
    v = jax.tree.map(lambda a: np.zeros(a.shape, np.float32), p)
    losses, grad1, used, gaps, worst = [], None, [], [], (0.0, "")

    for t in range(1, steps + 1):
        tokens = jnp.asarray(batches[t - 1], jnp.int32)
        inputs, targets = tokens[:, :-1], tokens[:, 1:]
        n_rows, length = inputs.shape
        rows = range(n_rows)

        def given(name, r):
            if choices is None or name not in routed:
                return None
            return jnp.asarray(choices[t - 1][routed.index(name)][r],
                               jnp.int32)

        xs, step_used = [[p["embed"]["embed"][inputs[r]] for r in rows]], {}
        for i, name in enumerate(order):
            outs = jax.block_until_ready(
                [_layer_fwd(p[name], x, bias.get(name, none), given(name, r),
                            hps[i], precision)
                 for r, x in zip(rows, xs[-1])])
            xs.append([x for x, _ in outs])
            if name not in routed:
                continue
            step_used[name] = np.stack([np.asarray(c) for _, (c, _) in outs])
            for r, (_, (_, gap)) in zip(rows, outs):
                gaps.append(float(gap[0]))
                if float(gap[1]) > worst[0]:
                    worst = (float(gap[1]), f"step {t} {name} row {r}")

        loss, g_head, dxs = 0.0, None, []
        for r in rows:
            l_r, (g_r, dx_r) = _head_vg(p["head"], xs[-1][r], targets[r],
                                        eps, n_rows * length, precision)
            loss = loss + l_r
            g_head = g_r if g_head is None else _tree_add(g_head, g_r)
            dxs.append(dx_r)
        xs.pop()
        losses.append(float(loss))

        norms = {}

        def update(name, g):
            p[name], m_new, v_new, norms[name] = _adamw(
                p[name], m[name], v[name], g, float(t), opt)
            m[name], v[name] = jax.device_get((m_new, v_new))

        update("head", g_head)
        for i in reversed(range(len(order))):
            name, g_layer, x_in = order[i], None, xs.pop()
            for r in rows:
                g_r, dxs[r] = _layer_bwd(
                    p[name], x_in[r], bias.get(name, none),
                    jnp.asarray(step_used[name][r]) if name in routed
                    else None, dxs[r], hps[i], precision)
                g_layer = g_r if g_layer is None else _tree_add(g_layer, g_r)
            update(name, g_layer)
            jax.block_until_ready(dxs)
        update("embed", {"embed": _embed_grad(
            inputs, jnp.stack(dxs), p["embed"]["embed"])})
        if t == 1:
            grad1 = _leaf_table(norms, table)

        # the correction bias, by the experts this step used
        for name in routed:
            load = np.bincount(step_used[name].reshape(-1),
                               minlength=n_experts)[:n_experts]
            under = np.sign(int(load.sum()) - n_experts * load.astype(
                np.int64)).astype(np.float32)
            bias[name] = bias[name] + jnp.asarray(rate * under)
        used.append(np.stack([step_used[n] for n in routed]))

    del m, v
    start = _groups(make_weights())[0]
    change = {g: {k: _diff_norm(p[g][k], start[g][k]) for k in p[g]}
              for g in p}
    return {"losses": losses, "grad1_norm": grad1,
            "change_norm": _leaf_table(change, table), "choices": used,
            "moe_bias": np.stack([np.asarray(bias[n]) for n in routed]),
            "routing_gap": (float(np.mean(gaps)) if gaps else 0.0,
                            "largest single token "
                            f"{worst[0]:.4g} at {worst[1]}")}
