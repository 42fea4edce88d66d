"""Plain reference of a training step of a decoder whose attention layers
are of two kinds in one model -- grouped-query softmax attention under a
causal window with one head count and rotary table, and over everything
before with another head count and a YaRN-scaled partial rotary table --
each with a head-wise sigmoid output gate, over a leading dense SwiGLU
layer and sparse expert layers with a scaled softmax router and an
ungated shared expert (Laguna-S-2.1's layers, written from its
``config.json`` and the papers its keys name; the modelling code is not
public here, so what the config does not state is the configuration
file's ``assumed``), on one expert-parallel rank's share.
Straightforward ``jax.numpy`` in float32 with every matrix
multiplication at ``highest`` precision; no kernels, no sorting, no
batching.  It imports nothing of the program under test (the helpers it
shares with the other references, and ``layer_plan``, are the
benchmark's own).

For one row ``x [S, d]``, positions ``0..S-1``, layer ``l`` of kind
``t`` with ``H_t`` query heads over ``K`` K/V heads of ``Dh`` columns:

  h = rmsnorm(x; ln1)                       (scale by w, eps as given)
  q = h Wq [S, H_t, Dh];  k = h Wk, v = h Wv [S, K, Dh]    (no bias, no
      norm over q and k)
  rotary, halves convention, on the first ``d_t`` columns of q and k:
      f_i = theta_t ** (-2 i / d_t), i < d_t / 2
      YaRN (factor s > 1, first trained for L0 positions):
        c(r) = d_t ln(L0 / (2 pi r)) / (2 ln theta_t)
        low = max(floor(c(beta_fast)), 0), high = min(ceil(c(beta_slow)),
        d_t - 1), ramp_i = clip((i - low) / (high - low), 0, 1)
        f_i <- (f_i / s) ramp_i + f_i (1 - ramp_i)
      cos and sin of (position f_i) times ``attention_factor``
  a_j = softmax(q_j k_{j // (H_t / K)}^T Dh^-1/2 + mask) v_{j // (H_t / K)}
      mask: key <= query, and under a window query - key < window
  g = sigmoid(h Wg) [S, H_t];  x = x + concat_j(g_j a_j) Wo
  h2 = rmsnorm(x; ln2)
  dense layer:   x = x + (silu(h2 W1) . h2 W3) W2
  expert layer:  p = softmax(h2 Wr);  S = the k largest
                 gate_e = p_e / sum_S p * route_scale
                 x = x + sum over HELD e in S of gate_e SwiGLU_e(h2)
                       + SwiGLU_shared(h2)
  loss = mean over the row of the next token's cross entropy, after the
  final rmsnorm and the untied head over the vocabulary slice

What absent experts would add is left out, as in the program; each held
expert runs over every position with its gate.  Attention is a masked
softmax a head and a block of 2,048 queries at a time, over the keys up
to the block's end (under a window: from the window of its first query
on), each pair under the mask itself.  Layers run one by one, forward
then backward.  Both Adam moments wait on the host between a group's
updates (6.5 GB at the cell's size): the chip holds the float32 weights
and one layer's working set at 16,384 positions.

Routing.  As ``gdn_gated_moe.follow``: handed the experts the program
chose (``choices``), it computes with those, its own probabilities as
gates, and holds every choice to its own probabilities: ``routing_gap``
is the mean over all tokens, layers and steps of log(own k-th largest
probability) - log(least probability among the experts given).

Controls (``follow``'s keywords), each the same code with one thing
changed: ``precision="fp8"``; ``window`` (1024, or 0: the window
dropped, the window layers causal); ``window_heads`` (48: heads 48 and
up of a window layer add nothing); ``attn_gate=False``; ``yarn=False``
(the scaled table plain: its theta, no interpolation, factor 1 on cos
and sin); ``rotary="all"`` (a partial table turns all of a head's
columns); ``route_scale`` (1.0); ``shared=False`` (no shared expert).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.harness.swa_moe_weights import layer_plan
from benchmarks.reference.dense_decoder import (_adamw, _diff_norm,
                                                _embed_grad, _head_vg, _mm,
                                                _rms_norm, _tree_add)
from benchmarks.reference.gdn_gated_moe import _flat
from benchmarks.reference.mla_moe_mtp import _leaf_table, _static, _swiglu

_F32 = jnp.float32
_QUERY_BLOCK = 2048


def rotary_frequencies(rope: dict, width: int) -> np.ndarray:
    """The ``width // 2`` angles a position of one of the
    configuration's ``rope_parameters`` groups over ``width`` rotated
    columns, float32; YaRN's blend where the group says so."""
    half = width // 2
    i = np.arange(half, dtype=np.float64)
    base = float(rope["rope_theta"])
    f = base ** (-2.0 * i / width)
    if rope.get("rope_type", "default") == "yarn":
        first = rope["original_max_position_embeddings"]

        def c(turns):
            return width * math.log(first / (2 * math.pi * turns)) / (
                2 * math.log(base))

        low = max(math.floor(c(rope["beta_fast"])), 0)
        high = min(math.ceil(c(rope["beta_slow"])), width - 1)
        ramp = np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
        f = f / rope["factor"] * ramp + f * (1.0 - ramp)
    return f.astype(np.float32)


def _rotary(x, freqs, scale):
    """x [S, H, D]: the halves of its first ``2 len(freqs)`` columns
    turned by position, the others passed."""
    width = 2 * freqs.shape[0]
    angles = jnp.arange(x.shape[0], dtype=_F32)[:, None] * freqs[None, :]
    cos = (jnp.cos(angles) * scale)[:, None, :]
    sin = (jnp.sin(angles) * scale)[:, None, :]
    x1, x2 = x[..., :width // 2], x[..., width // 2:width]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos,
                            x[..., width:]], axis=-1)


def _attend(q, k, v, scale, window, precision):
    """q, k [S, H, Dk], v [S, H, Dv]: masked softmax attention, one
    block of 2,048 queries and one head at a time, over the keys the
    block's mask can allow at all -- those at or before its last query
    and, under a window, within the window of its first: a slice by
    position, then the mask itself on every pair.  ``window`` None:
    causal."""
    s = q.shape[0]
    block = _QUERY_BLOCK if s % _QUERY_BLOCK == 0 else s
    out = []
    for start in range(0, s, block):
        stop = start + block
        first = 0 if window is None else max(0, start - window + 1)
        q_pos = jnp.arange(start, stop)[:, None]
        k_pos = jnp.arange(first, stop)[None, :]
        allowed = q_pos >= k_pos
        if window is not None:
            allowed &= q_pos - k_pos < window

        def one_head(qkv, allowed=allowed):
            qh, kh, vh = qkv
            scores = _mm(precision, "qd,kd->qk", qh, kh) * scale
            probs = jax.nn.softmax(jnp.where(allowed, scores, -jnp.inf),
                                   axis=-1)
            return _mm(precision, "qk,kd->qd", probs, vh)

        heads = jax.lax.map(jax.checkpoint(one_head), (
            q[start:stop].transpose(1, 0, 2),
            k[first:stop].transpose(1, 0, 2),
            v[first:stop].transpose(1, 0, 2)))
        out.append(heads.transpose(1, 0, 2))
    return jnp.concatenate(out, axis=0)


def _attention(lp, h, hp, precision):
    dh = lp["wq"].shape[-1]
    q = _mm(precision, "sd,dhk->shk", h, lp["wq"])
    k = _mm(precision, "sd,dhk->shk", h, lp["wk"])
    v = _mm(precision, "sd,dhk->shk", h, lp["wv"])
    freqs = jnp.asarray(hp["freqs"], _F32)
    q = _rotary(q, freqs, hp["rope_scale"])
    k = _rotary(k, freqs, hp["rope_scale"])
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    a = _attend(q, k, v, dh ** -0.5, hp["window"], precision)
    if hp["heads_used"] is not None:
        a = a * (jnp.arange(a.shape[1]) < hp["heads_used"])[None, :, None]
    if hp["attn_gate"]:
        a = a * jax.nn.sigmoid(
            _mm(precision, "sd,dh->sh", h, lp["wg"]))[..., None]
    return _mm(precision, "shk,hkd->sd", a, lp["wo"])


def _experts(lp, h, hp, precision, given):
    """The expert layer on ``h [S, d]`` -> (y, (the experts used [S, k],
    this row's routing gap as (mean, largest)))."""
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
    gate = gate * hp["route_scale"]

    def one_expert(y, ew):
        e, w1, w3, w2 = ew
        g = jnp.sum(jnp.where(chosen == first + e, gate, 0.0), axis=-1)
        return y + g[:, None] * _swiglu(h, w1, w3, w2, precision), None

    held = lp["moe.w1"].shape[0]
    y, _ = jax.lax.scan(jax.checkpoint(one_expert), jnp.zeros_like(h),
                        (jnp.arange(held), lp["moe.w1"], lp["moe.w3"],
                         lp["moe.w2"]))
    if hp["shared"]:
        y = y + _swiglu(h, lp["moe.ws1"], lp["moe.ws3"], lp["moe.ws2"],
                        precision)
    return y, (chosen, gap)


def layer(lp: dict, x, hp: dict, precision: str, given=None):
    """One block on one row ``x [S, d]`` -> (x, (experts used, routing
    gap)); a dense layer (``w1`` among its leaves) routes nothing."""
    x = x + _attention(lp, _rms_norm(x, lp["ln1"], hp["eps"]), hp, precision)
    h = _rms_norm(x, lp["ln2"], hp["eps"])
    if "w1" in lp:
        none = (jnp.zeros((x.shape[0], 0), jnp.int32), jnp.zeros((2,), _F32))
        return x + _swiglu(h, lp["w1"], lp["w3"], lp["w2"], precision), none
    y, routed = _experts(lp, h, hp, precision, given)
    return x + y, routed


@functools.partial(jax.jit, static_argnames=("hp", "precision"))
def _layer_fwd(lp, x, given, hp, precision):
    return layer(lp, x, dict(hp), precision, given)


@functools.partial(jax.jit, static_argnames=("hp", "precision"))
def _layer_bwd(lp, x, given, dy, hp, precision):
    """``given``: the experts the forward used (its own or the
    program's), so both passes route alike."""
    _, vjp, _ = jax.vjp(lambda p, a: layer(p, a, dict(hp), precision, given),
                        lp, x, has_aux=True)
    return vjp(dy)                                      # (d lp, d x)


def _groups(weights: dict):
    """The seed's tree -> (float32 update groups, the table's rows, the
    layers' names in order).  A row of the table: (label, [(group,
    leaf), ...]) as the program's leaves reduce -- a run's stack by
    layer, a period's by (period, layer of the run), flattened."""
    groups = {"embed": {"embed": weights["embed"].astype(_F32)},
              "head": {"ln_f": weights["ln_f"].astype(_F32),
                       "lm_head": weights["lm_head"].astype(_F32)}}
    table = [("embed", [("embed", "embed")]), ("ln_f", [("head", "ln_f")]),
             ("lm_head", [("head", "lm_head")])]
    order = []

    def take(stack, at, label, members):
        name = f"layer{len(order)}"
        groups[name] = _flat(stack, at)
        order.append(name)
        members.setdefault(label, []).append(name)

    for e, entry in enumerate(weights["layers"]):
        members: dict = {}
        if isinstance(entry, dict):
            for j in range(jax.tree.leaves(entry)[0].shape[0]):
                take(entry, (j,), f"layers.{e}", members)
        else:
            repeats = jax.tree.leaves(entry[0])[0].shape[0]
            for p in range(repeats):
                for i, run in enumerate(entry):
                    for j in range(jax.tree.leaves(run)[0].shape[1]):
                        take(run, (p, j), f"layers.{e}.{i}", members)
        for label, names in members.items():
            table += [(f"{label}.{leaf}", [(n, leaf) for n in names])
                      for leaf in groups[names[0]]]
    return groups, table, order


def layer_hps(cfg: dict, window=None, window_heads=None,
              attn_gate: bool = True, yarn: bool = True,
              rotary: str = "partial", route_scale=None,
              shared: bool = True) -> list:
    """What each layer of ``layer_plan`` is, as the static argument of
    its compiled functions; the keywords are the controls'."""
    dh = cfg["head_dim"]
    out = []
    for entry in layer_plan(cfg):
        rope = dict(cfg["rope_parameters"][entry["type"]])
        width = int(dh * rope.get("partial_rotary_factor", 1))
        if rotary == "all":
            width = dh
        scaled = rope.get("rope_type", "default") == "yarn"
        if scaled and not yarn:
            rope["rope_type"] = "default"
        sliding = entry["window"] is not None
        hp = {"eps": cfg["rms_norm_eps"],
              "freqs": tuple(float(f) for f in
                             rotary_frequencies(rope, width)),
              "rope_scale": float(rope.get("attention_factor", 1.0))
              if scaled and yarn else 1.0,
              "window": entry["window"], "heads_used": None,
              "attn_gate": attn_gate, "top_k": cfg["num_experts_per_tok"],
              "norm_topk": bool(cfg["norm_topk_prob"]),
              "first": cfg["experts_held_first"],
              "route_scale": float(cfg["moe_routed_scaling_factor"]
                                   if route_scale is None else route_scale),
              "shared": shared}
        if sliding and window is not None:
            hp["window"] = window or None
        if sliding and window_heads is not None:
            hp["heads_used"] = window_heads
        out.append(_static(hp))
    return out


def follow(make_weights, batches, cfg: dict, steps: int = 2,
           precision: str = "float32", learning_rate=None, choices=None,
           **controls) -> dict:
    """Train ``steps`` steps from the seed's weights.  ``batches[t]`` is
    ``[rows, S + 1]`` int tokens.  Returns each step's loss, the first
    gradient's norm per leaf and the norm per leaf of the parameters'
    change over the steps, labelled as the program's tree flattens;
    ``choices`` (per step ``[expert layers, rows, S, k]``, the experts
    used) and ``routing_gap`` (value, note).  ``choices`` in: the
    program's, to be followed.  ``controls``: ``layer_hps``' keywords."""
    o = cfg["optimizer"]
    lr = o["learning_rate"] if learning_rate is None else learning_rate
    opt = (lr, o["b1"], o["b2"], o["eps"], o["weight_decay"])
    eps = cfg["rms_norm_eps"]
    hps = layer_hps(cfg, **controls)

    p, table, order = _groups(make_weights())
    if len(order) != len(hps):
        raise ValueError(f"{len(order)} layers of weights for a plan of "
                         f"{len(hps)}")
    sparse = [i for i, name in enumerate(order) if "moe.wr" in p[name]]
    m = jax.tree.map(lambda a: np.zeros(a.shape, np.float32), p)   # host
    v = jax.tree.map(lambda a: np.zeros(a.shape, np.float32), p)
    losses, grad1, used, gaps, worst = [], None, [], [], (0.0, "")
    n_layers = len(order)

    for t in range(1, steps + 1):
        tokens = jnp.asarray(batches[t - 1], jnp.int32)
        inputs, targets = tokens[:, :-1], tokens[:, 1:]
        n_rows, length = inputs.shape
        rows = range(n_rows)
        n_tokens = n_rows * length

        def given(i, r):
            if choices is None or i not in sparse:
                return None
            return jnp.asarray(choices[t - 1][sparse.index(i)][r], jnp.int32)

        xs = [[p["embed"]["embed"][inputs[r]] for r in rows]]
        step_used = {}
        for i, name in enumerate(order):
            outs = jax.block_until_ready(
                [_layer_fwd(p[name], x, given(i, r), hps[i], precision)
                 for r, x in zip(rows, xs[-1])])
            xs.append([x for x, _ in outs])
            if i not in sparse:
                continue
            step_used[i] = np.stack([np.asarray(c) for _, (c, _) in outs])
            for r, (_, (_, gap)) in zip(rows, outs):
                gaps.append(float(gap[0]))
                if float(gap[1]) > worst[0]:
                    worst = (float(gap[1]), f"step {t} layer {i} row {r}")
        used.append(np.stack([step_used[i] for i in sparse]))

        loss, g_head, dxs = 0.0, None, []
        for r in rows:
            l_r, (g_r, dx_r) = _head_vg(p["head"], xs[-1][r], targets[r],
                                        eps, n_tokens, precision)
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
        for i in reversed(range(n_layers)):
            name, g_layer, x_in = order[i], None, xs.pop()
            for r in rows:
                g_r, dxs[r] = _layer_bwd(
                    p[name], x_in[r],
                    jnp.asarray(step_used[i][r]) if i in sparse else None,
                    dxs[r], hps[i], precision)
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
    return {"losses": losses, "grad1_norm": grad1,
            "change_norm": _leaf_table(change, table), "choices": used,
            "routing_gap": (float(np.mean(gaps)) if gaps else 0.0,
                            "largest single token "
                            f"{worst[0]:.4g} at {worst[1]}")}
