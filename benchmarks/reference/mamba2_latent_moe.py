"""Plain reference of a training step of a decoder whose layers are each
one sublayer -- a Mamba-2 mixer, grouped-query attention without
positional encoding, or an expert layer of gate-less squared-ReLU
experts in a latent beside a full-width shared expert, with a sigmoid
router and its correction bias -- as Nemotron-H lays them out
(``hybrid_override_pattern``), written from its ``config.json`` and
``modeling_nemotron_h.py`` as the configuration file's ``assumed`` has
them, on one expert-parallel rank's share.  Straightforward
``jax.numpy`` in float32 with every matrix multiplication at
``highest`` precision; no kernels, no chunks, no sorting.  It imports
nothing of the program under test (the helpers it shares with the other
references, and ``layer_plan``, are the benchmark's own).

For one row ``x [S, d]``, each sublayer ``x <- x + f(rmsnorm(x))``
(weight ``w``, eps ``layer_norm_epsilon``), the program's layer being a
mixer and, where the published pattern has one after it, an expert
layer:

  Mamba-2 (H heads of P, G groups of N states, K taps):
    z = h Wz [S, H P];  xBC = h Wxbc [S, H P + 2 G N];  dt = h Wdt [S, H]
    xBC = silu(sum_j xBC[t - (K - 1 - j)] conv_j + conv_b)   zeros before
    x, B, C = xBC split [S, H, P], [S, G, N], [S, G, N]
    dt = softplus(dt + dt_bias);  a = -exp(A_log)
    S_t = exp(dt_t a) S_{t-1} + B_{t, h // (H / G)}^T (dt_t x_t)   [N, P]
          float32, token by token (``ssd_recurrence``)
    y_t = C_{t, h // (H / G)} S_t + D x_t
    y = rmsnorm over each of ``n_groups`` groups of H P / n_groups
        columns of (y silu(z)), times ``norm``;  out = y Wout
  attention (Hq query heads over K/V heads of Dh):
    a_j = softmax(q_j k_{j // (Hq / Hkv)}^T Dh^-1/2 + causal) v_..,
    no rotary;  out = concat_j a_j Wo
  experts:
    s = sigmoid(h Wr) [E];  chosen = the k largest of s + bias
    gate_e = s_e / (sum over chosen s + 1e-20) * route_scale
    u = h W_down [S, latent]
    out = (sum over HELD e in chosen of gate_e relu(u W1_e)^2 W2_e) W_up
          + relu(h Ws1)^2 Ws2
  loss = mean over the row of the next token's cross entropy, after the
  final rmsnorm and the untied head over the vocabulary slice

What absent experts would add is left out, as in the program; each held
expert runs over every position with its gate.  Layers run one by one,
forward then backward, a row at a time; both Adam moments wait on the
host between a group's updates (9.7 GB at the cell's size): the chip
holds the float32 weights and one layer's working set.

Routing and the bias.  As ``mla_moe_mtp.follow``: handed the experts the
program chose (``choices``), it computes with those, its own scores as
gates, and holds every choice to its own ``score + bias``
(``routing_gap``); the bias moves after each step by the experts that
step used, by ``router_bias_update_rate``.

The rule alone (``rule_probe``): the recurrence and its ``jax.vjp`` on a
seeded probe at the step's shape, whose heads' ``dt a`` span 1e-4 to 16
a position, against which ``rule_gaps`` holds the program's kernels.

Controls (``follow``'s keywords), each the same code with one thing
changed: ``precision="fp8"``; ``state="bfloat16"`` (the state rounded to
bfloat16 after every position); ``decay=False`` (``a`` = 0);
``skip=False`` (no ``D x``); ``norm_groups=1`` (one norm over all ``H
P`` columns); ``act="relu"`` (ReLU for ReLU^2, experts and shared
expert); ``latent=False`` (no latent pair: the experts read the first
``latent`` columns of ``h`` and their sum is written to the first
``latent`` columns of the output).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.harness.mamba2_moe_weights import layer_plan
from benchmarks.harness.weights import seed_key
from benchmarks.reference.dense_decoder import (_adamw, _diff_norm,
                                                _embed_grad, _head_vg, _mm,
                                                _rms_norm, _tree_add)
from benchmarks.reference.gdn_gated_moe import _rounded, _shifted
from benchmarks.reference.mla_moe_mtp import _groups, _leaf_table, _static
from benchmarks.reference.swa_gqa_moe import _attend

_F32 = jnp.float32
_SCAN_BLOCK = 64


def ssd_recurrence(x, dt, a, b, c, state_dtype=None):
    """x [S, H, P], dt [S, H], a [H], b, c [S, G, N] -> y [S, H, P]
    without the ``D`` skip: every head's state token by token, float32
    (rounded to ``state_dtype`` after every position where given); the
    positions in checkpointed blocks of 64, so the gradient keeps a
    state a block."""
    length, h, p = x.shape
    r = h // b.shape[1]
    block = _SCAN_BLOCK if length % _SCAN_BLOCK == 0 else length

    def step(s, at):
        x_t, dt_t, b_t, c_t = at
        b_t, c_t = jnp.repeat(b_t, r, axis=0), jnp.repeat(c_t, r, axis=0)
        s = jnp.exp(dt_t * a)[:, None, None] * s \
            + b_t[:, :, None] * (dt_t[:, None] * x_t)[:, None, :]
        if state_dtype is not None:
            s = _rounded(s, state_dtype)
        return s, jnp.einsum("hn,hnp->hp", c_t, s,
                             precision=jax.lax.Precision.HIGHEST)

    @jax.checkpoint
    def one_block(s, at):
        return jax.lax.scan(step, s, at)

    def blocks(v):
        return v.reshape(length // block, block, *v.shape[1:])

    _, y = jax.lax.scan(one_block, jnp.zeros((h, b.shape[-1], p), _F32),
                        tuple(blocks(v) for v in (x, dt, b, c)))
    return y.reshape(length, h, p)


def _mixer(lp, h, hp, precision):
    """The Mamba-2 mixer on ``h [S, d]``."""
    heads, p, g, n = hp["heads"], hp["head_dim"], hp["groups"], hp["state"]
    s, hp_, gn = h.shape[0], heads * p, g * n
    z = _mm(precision, "sd,de->se", h, lp["mamba2.w_z"])
    xbc = _mm(precision, "sd,de->se", h, lp["mamba2.w_xbc"])
    dt = _mm(precision, "sd,de->se", h, lp["mamba2.w_dt"])
    taps = lp["mamba2.conv"]                                # [C, K]
    k = taps.shape[-1]
    xbc = jax.nn.silu(sum(_shifted(xbc, k - 1 - j) * taps[:, j]
                          for j in range(k)) + lp["mamba2.conv_b"])
    x = xbc[:, :hp_].reshape(s, heads, p)
    b = xbc[:, hp_:hp_ + gn].reshape(s, g, n)
    c = xbc[:, hp_ + gn:].reshape(s, g, n)
    dt = jax.nn.softplus(dt + lp["mamba2.dt_bias"])
    a = -jnp.exp(lp["mamba2.A_log"])
    if not hp["decay"]:
        a = jnp.zeros_like(a)
    y = ssd_recurrence(x, dt, a, b, c, jnp.bfloat16
                       if hp["ssd_state"] == "bfloat16" else None)
    if hp["skip"]:
        y = y + lp["mamba2.D"][:, None] * x
    gated = (y.reshape(s, hp_) * jax.nn.silu(z)).reshape(
        s, hp["norm_groups"], -1)
    gated = gated * jax.lax.rsqrt(jnp.mean(gated * gated, axis=-1,
                                           keepdims=True) + hp["eps"])
    return _mm(precision, "se,ed->sd", gated.reshape(s, hp_) *
               lp["mamba2.norm"], lp["mamba2.w_out"])


def _attention(lp, h, hp, precision):
    dh = lp["wq"].shape[-1]
    q = _mm(precision, "sd,dhk->shk", h, lp["wq"])
    k = _mm(precision, "sd,dhk->shk", h, lp["wk"])
    v = _mm(precision, "sd,dhk->shk", h, lp["wv"])
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    a = _attend(q, k, v, dh ** -0.5, None, precision)
    return _mm(precision, "shk,hkd->sd", a, lp["wo"])


def _act(x, hp):
    x = jax.nn.relu(x)
    return x * x if hp["act"] == "relu2" else x


def _experts(lp, h, bias, hp, precision, given):
    """The expert layer on ``h [S, d]`` -> (y, the experts used [S, k],
    this row's routing gap as (mean, largest))."""
    top_k, first, latent = hp["top_k"], hp["first"], hp["latent"]
    score = jax.nn.sigmoid(_mm(precision, "sd,de->se", h, lp["moe.wr"]))
    select = score + jax.lax.stop_gradient(bias)
    own, chosen = jax.lax.top_k(select, top_k)                 # [S, k]
    gap = jnp.zeros((2,), _F32)
    if given is not None:
        chosen = given
        in_order = jnp.sort(chosen, axis=-1)
        distinct = jnp.all(in_order[:, 1:] > in_order[:, :-1], axis=-1) & \
            (in_order[:, 0] >= 0) & (in_order[:, -1] < score.shape[-1])
        least = jnp.min(jnp.take_along_axis(select, chosen, axis=-1), axis=-1)
        short = jnp.where(distinct, own[:, -1] - least, jnp.inf)
        gap = jax.lax.stop_gradient(
            jnp.stack([jnp.mean(short), jnp.max(short)]))
    gate = jnp.take_along_axis(score, chosen, axis=-1)
    gate = gate / (jnp.sum(gate, axis=-1, keepdims=True) + 1e-20) \
        * hp["route_scale"]
    width = lp["moe.w1"].shape[1]
    u = _mm(precision, "sd,dl->sl", h, lp["moe.w_down"]) if latent \
        else h[:, :width]

    def one_expert(y, ew):
        e, w1, w2 = ew
        g = jnp.sum(jnp.where(chosen == first + e, gate, 0.0), axis=-1)
        hidden = _act(_mm(precision, "sl,lf->sf", u, w1), hp)
        return y + g[:, None] * _mm(precision, "sf,fl->sl", hidden, w2), None

    held = lp["moe.w1"].shape[0]
    y, _ = jax.lax.scan(jax.checkpoint(one_expert), jnp.zeros_like(u),
                        (jnp.arange(held), lp["moe.w1"], lp["moe.w2"]))
    if latent:
        y = _mm(precision, "sl,ld->sd", y, lp["moe.w_up"])
    else:
        y = jnp.pad(y, ((0, 0), (0, h.shape[1] - width)))
    shared = _act(_mm(precision, "sd,df->sf", h, lp["moe.ws1"]), hp)
    return y + _mm(precision, "sf,fd->sd", shared, lp["moe.ws2"]), chosen, gap


def layer(lp: dict, x, bias, hp: dict, precision: str, given=None):
    """One of the program's layers on one row ``x [S, d]`` -> (x,
    (experts used, routing gap)): the mixer's sublayer, then the expert
    layer's where the layer has one."""
    h = _rms_norm(x, lp["ln1"], hp["eps"])
    mixer = _mixer if hp["mixer"] == "mamba2" else _attention
    x = x + mixer(lp, h, hp, precision)
    if "ln2" not in lp:
        return x, (jnp.zeros((x.shape[0], hp["top_k"]), jnp.int32),
                   jnp.zeros((2,), _F32))
    y, chosen, gap = _experts(lp, _rms_norm(x, lp["ln2"], hp["eps"]), bias,
                              hp, precision, given)
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

#: The output and the four gradients compared; the rates' own gradient
#: is left out: a head's is one sum over the row whose terms cancel where
#: the head forgets within a position (float32 against float32 reads
#: 2.5e-3 there), and what it sums is ``ddt``'s.
PROBE_PARTS = ("y", "dx", "ddt", "db", "dc")


@functools.partial(jax.jit, static_argnames=("shape", "dtype"))
def _probe_draw(key, shape, dtype):
    rows, length, h, p, g, n = shape
    kx, kd, kb, kc, ky = jax.random.split(key, 5)
    # dt a: a head's rate, 1e-4 (remembers the whole row) to 16 (forgets
    # within a position), spaced evenly in the logarithm
    rate = jnp.exp(jnp.linspace(math.log(1e-4), math.log(16.0), h))
    dt = rate * jax.nn.softplus(
        jax.random.normal(kd, (rows, length, h), _F32) + 1.0) / 1.5

    def rounded(k, shape, scale=1.0):
        return (scale * jax.random.normal(k, shape, _F32)).astype(
            dtype).astype(_F32)

    return (rounded(kx, (rows, length, h, p)), dt, -jnp.ones((h,), _F32),
            rounded(kb, (rows, length, g, n), n ** -0.5),
            rounded(kc, (rows, length, g, n), n ** -0.5),
            rounded(ky, (rows, length, h, p)))


def rule_probe_inputs(seed: int, cfg: dict, rows: int, length: int):
    """(x, dt, a, b, c, dy) for ``rows`` rows of ``length`` positions at
    the configuration's heads, groups and states, float32, drawn on the
    device from the seed: x and the output's cotangent ~ N(0, 1), B and
    C ~ N(0, 1 / N), all four rounded to the configuration's type (what
    both sides are handed); ``a = -1`` a head and ``dt = rate_h
    softplus(N(0, 1) + 1) / 1.5`` with the heads' rates spaced evenly in
    the logarithm from 1e-4 to 16."""
    key = jax.random.fold_in(seed_key(seed), int.from_bytes(b"ssd", "little"))
    return _probe_draw(key, (rows, length, cfg["mamba_num_heads"],
                             cfg["mamba_head_dim"], cfg["n_groups"],
                             cfg["ssm_state_size"]), jnp.dtype(cfg["dtype"]))


@functools.partial(jax.jit, static_argnames=("state", "decay"))
def _rule_probe_row(x, dt, a, b, c, dy, state, decay):
    def rule(x, dt, b, c):
        return ssd_recurrence(x, dt, a if decay else jnp.zeros_like(a), b, c,
                              jnp.bfloat16 if state == "bfloat16" else None)

    y, vjp = jax.vjp(rule, x, dt, b, c)
    return (y, *vjp(dy))


def rule_probe(inputs, state: str = "float32", decay: bool = True) -> dict:
    """The recurrence and its ``jax.vjp`` on a probe's ``inputs``, a row
    at a time -> ``PROBE_PARTS`` as float32 arrays on the host."""
    x, dt, a, b, c, dy = inputs
    rows = [_rule_probe_row(x[r], dt[r], a, b[r], c[r], dy[r], state, decay)
            for r in range(x.shape[0])]
    return {name: np.stack([np.asarray(row[i]) for row in rows])
            for i, name in enumerate(PROBE_PARTS)}


def _head_gaps(prog, ref, axis):
    """``|prog - ref| / |ref|`` a head (``axis``; None: whole)."""
    prog = np.asarray(prog).astype(np.float64)
    ref = np.asarray(ref, np.float64)
    over = None if axis is None else tuple(
        i for i in range(ref.ndim) if i != axis)
    gap = np.sqrt(np.sum((prog - ref) ** 2, axis=over)
                  / np.maximum(np.sum(ref ** 2, axis=over), 1e-300))
    gap = np.atleast_1d(np.where(np.isfinite(gap), gap, np.inf))
    at = int(np.argmax(gap))
    return float(gap[at]), at, gap.size


def rule_gaps(prog: dict, ref: dict) -> dict:
    """Two probes (``PROBE_PARTS``) -> ``ssd_rule_gap``, the worst
    head's ``|prog - ref| / |ref|`` of the output, and
    ``ssd_rule_grad_gap``, the worst head's (a group's for ``db``,
    ``dc``) of the four gradients, each (gap, which part and where)."""
    axes = {"y": 2, "dx": 2, "ddt": 2, "db": 2, "dc": 2}
    worst = {}
    for name in PROBE_PARTS:
        if prog[name].shape != ref[name].shape:
            worst[name] = (math.inf, f"{name}: {prog[name].shape} against "
                           f"{ref[name].shape}")
            continue
        gap, at, of = _head_gaps(prog[name], ref[name], axes[name])
        worst[name] = (gap, f"{name}, {at} of {of}")
    return {"ssd_rule_gap": worst["y"],
            "ssd_rule_grad_gap": max((worst[name] for name in
                                      PROBE_PARTS[1:]), key=lambda w: w[0])}


# ---- the steps -------------------------------------------------------------

def layer_hps(cfg: dict, state: str = "float32", decay: bool = True,
              skip: bool = True, norm_groups=None, act=None,
              latent: bool = True) -> list:
    """What each layer of ``layer_plan`` is, as the static argument of
    its compiled functions; the keywords are the controls'."""
    out = []
    for entry in layer_plan(cfg):
        out.append(_static({
            "mixer": entry["mixer"], "eps": cfg["layer_norm_epsilon"],
            "heads": cfg["mamba_num_heads"], "head_dim": cfg["mamba_head_dim"],
            "groups": cfg["n_groups"], "state": cfg["ssm_state_size"],
            "norm_groups": cfg["n_groups"] if norm_groups is None
            else norm_groups,
            "ssd_state": state, "decay": decay, "skip": skip,
            "top_k": cfg["num_experts_per_tok"],
            "route_scale": float(cfg["routed_scaling_factor"]),
            "first": cfg["experts_held_first"],
            "act": cfg["mlp_hidden_act"] if act is None else act,
            "latent": latent}))
    return out


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
    eps = cfg["layer_norm_epsilon"]
    rate = np.float32(cfg["router_bias_update_rate"])
    n_experts = cfg["n_routed_experts"]
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
