"""Plain reference of a training step of a latent-attention sparse-expert
decoder with a multi-token-prediction module (DeepSeek-V3's layers, as
JoyAI-LLM-Flash's ``config.json`` follows them key for key), on one
expert-parallel rank's share.  Straightforward ``jax.numpy`` in float32
with every matrix multiplication at ``highest`` precision; no kernels,
no sorting, no batching.  It imports nothing of the program under test
(the helpers it shares with ``dense_decoder.py`` are the benchmark's
own).

For one row ``x [S, d]``, positions ``0..S-1``, ``H`` heads:

  attention (every layer), ``h = rmsnorm(x; ln1)``:
    c_q        = rmsnorm(h Wq_a; q_norm)                  [rq]
    q_n | q_r  = c_q Wq_b                                 H x (dn | dr)
    c_kv | k_r = h Wkv_a                                  [rkv | dr]
    k_n | v    = rmsnorm(c_kv; kv_norm) Wkv_b             H x (dn | dv)
    q_r, k_r   = rope over interleaved pairs (x[2i], x[2i+1]); k_r is
                 ONE head, read by every query head
    s          = (q_n . k_n + q_r . k_r) (dn + dr)^-1/2, causal softmax
    x          = x + (softmax(s) v, heads joined) Wo
  FFN, ``h = rmsnorm(x; ln2)``:
    the first ``first_k_dense_replace`` layers: x + SwiGLU(h)
    the others: score = sigmoid(h Wr) [E]; S = the k largest of
    score + b (b: the layer's correction bias, no gradient);
    g_e = score_e / (sum_S score + 1e-20) * routed_scaling_factor;
    x + sum over HELD e in S of g_e SwiGLU_e(h) + SwiGLU_shared(h)
  multi-token prediction, with h the last layer's output BEFORE ln_f:
    h'  = [rmsnorm(h; hnorm) ; rmsnorm(embed[t_{i+1}]; enorm)] W_eh
    h'' = one more expert layer (own attention, router, experts,
          shared expert, bias); z = rmsnorm(h''; the module's ln_f)
          lm_head (shared); cross entropy against t_{i+2}, mean over
          the S - 1 positions a row that have one
  loss = main + lambda * mtp
  after the step, for every expert layer: b += gamma * sign(sum(load)
  - E * load), load the step's choices of each of the E experts.

What absent experts would add is left out, as in the program; each held
expert runs over every position with its gate (nought where it was not
chosen).

So that it fits beside nothing else on one 16 GB chip it works layer by
layer and row by row like ``dense_decoder.follow``; the module's
projection, layer and head are three such pieces.

Routing.  As ``block_diffusion_moe.follow``: handed the experts the
program chose (``choices``), it computes with those, its own scores as
gates, and holds every choice to its own ``score + b``: a token falls
short by (its own k-th largest ``score + b``) - (the least ``score + b``
among the experts it was given): 0 where they are its own top k, the
size of the tie where two swapped.  ``routing_gap`` is the mean of that
over all tokens, expert layers and steps (infinite for a repeated or
absent expert).  The loads, and so the bias, are counted from the
experts used.

Controls (``follow``'s keywords), each the same code with one thing
changed: ``precision="fp8"``; ``rotary=False`` (the rotary columns left
out of the score); ``scoring="softmax"``; ``mtp_coeff=0.0`` (the module's
loss left out); ``shared=False`` (the shared expert left out).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference.dense_decoder import (_adamw, _diff_norm, _mm,
                                                _rms_norm, _tree_add)

_F32 = jnp.float32


def _rope_interleaved(x, theta):
    """x [S, H, R]: rotate the pairs (x[2i], x[2i+1]) by position *
    theta^(-2i/R); the pairs stay where they are."""
    s, _, r = x.shape
    half = r // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=_F32) / half)
    angles = jnp.arange(s, dtype=_F32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    pairs = x.reshape(s, -1, half, 2)
    x1, x2 = pairs[..., 0], pairs[..., 1]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     axis=-1).reshape(x.shape)


def _attend_causal(q, k, v, scale, precision):
    """q, k [S, H, Dk], v [S, H, Dv]: causal softmax attention, one
    head at a time."""
    s = q.shape[0]
    causal = jnp.tril(jnp.ones((s, s), bool))

    def one_head(qkv):
        qh, kh, vh = qkv
        scores = _mm(precision, "qd,kd->qk", qh, kh) * scale
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return _mm(precision, "qk,kd->qd", probs, vh)

    heads = jax.lax.map(jax.checkpoint(one_head),
                        tuple(t.transpose(1, 0, 2) for t in (q, k, v)))
    return heads.transpose(1, 0, 2)


def _latent_attention(lp, h, hp, precision):
    dn, rkv, eps = hp["qk_nope"], hp["kv_rank"], hp["eps"]
    c_q = _rms_norm(_mm(precision, "sd,dr->sr", h, lp["mla.wq_a"]),
                    lp["mla.q_norm"], eps)
    q = _mm(precision, "sr,rhk->shk", c_q, lp["mla.wq_b"])
    latent = _mm(precision, "sd,dr->sr", h, lp["mla.wkv_a"])
    c_kv = _rms_norm(latent[:, :rkv], lp["mla.kv_norm"], eps)
    kv = _mm(precision, "sr,rhk->shk", c_kv, lp["mla.wkv_b"])
    q_n, k_n, v = q[..., :dn], kv[..., :dn], kv[..., dn:]
    scale = q.shape[-1] ** -0.5
    if hp["rotary"]:
        q_r = _rope_interleaved(q[..., dn:], hp["theta"])
        k_r = _rope_interleaved(latent[:, None, rkv:], hp["theta"])
        q_n = jnp.concatenate([q_n, q_r], axis=-1)
        k_n = jnp.concatenate(
            [k_n, jnp.broadcast_to(k_r, k_n.shape[:2] + k_r.shape[2:])],
            axis=-1)
    return _mm(precision, "shk,hkd->sd",
               _attend_causal(q_n, k_n, v, scale, precision), lp["mla.wo"])


def _swiglu(h, w1, w3, w2, precision):
    act = jax.nn.silu(_mm(precision, "sd,df->sf", h, w1)) * \
        _mm(precision, "sd,df->sf", h, w3)
    return _mm(precision, "sf,fd->sd", act, w2)


def _experts(lp, h, bias, hp, precision, given):
    """The expert layer on ``h [S, d]`` -> (y, the experts used [S, k],
    this row's routing gap as (mean, largest))."""
    top_k, first = hp["top_k"], hp["first"]
    logits = _mm(precision, "sd,de->se", h, lp["moe.wr"])
    score = jax.nn.sigmoid(logits) if hp["scoring"] == "sigmoid" \
        else jax.nn.softmax(logits, axis=-1)
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
    if hp["norm_topk"]:
        gate = gate / (jnp.sum(gate, axis=-1, keepdims=True) + 1e-20)
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
    return y, chosen, gap


def layer(lp: dict, x, bias, hp: dict, precision: str, given=None):
    """One block on one row ``x [S, d]`` -> (x, (experts used, routing
    gap)); a dense layer uses no experts."""
    x = x + _latent_attention(lp, _rms_norm(x, lp["ln1"], hp["eps"]), hp,
                              precision)
    h = _rms_norm(x, lp["ln2"], hp["eps"])
    if "moe.wr" not in lp:
        return x + _swiglu(h, lp["w1"], lp["w3"], lp["w2"], precision), \
            (jnp.zeros((x.shape[0], hp["top_k"]), jnp.int32),
             jnp.zeros((2,), _F32))
    y, chosen, gap = _experts(lp, h, bias, hp, precision, given)
    return x + y, (chosen, gap)


def head_loss(hp_: dict, x, targets, weight, eps, n_tokens, precision):
    """This row's part of a cross entropy: the weighted sum over its
    positions, over the batch's count."""
    logits = _mm(precision, "sd,dv->sv", _rms_norm(x, hp_["ln_f"], eps),
                 hp_["lm_head"])
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
    return jnp.sum(weight * (logz - gold)) / n_tokens


def mtp_join(jp: dict, h, e, eps, precision):
    """[rmsnorm(h) ; rmsnorm(embedding of the next token)] W_eh."""
    joined = jnp.concatenate([_rms_norm(h, jp["hnorm"], eps),
                              _rms_norm(e, jp["enorm"], eps)], axis=-1)
    return _mm(precision, "se,ed->sd", joined, jp["w_eh"])


def _static(hp):
    return tuple(sorted(hp.items()))


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


@functools.partial(jax.jit,
                   static_argnames=("eps", "n_tokens", "precision"))
def _head_vg(hp_, x, targets, weight, scale, eps, n_tokens, precision):
    return jax.value_and_grad(
        lambda p, a: scale * head_loss(p, a, targets, weight, eps, n_tokens,
                                       precision), argnums=(0, 1))(hp_, x)


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _join_fwd(jp, h, e, eps, precision):
    return mtp_join(jp, h, e, eps, precision)


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _join_bwd(jp, h, e, dy, eps, precision):
    _, vjp = jax.vjp(lambda p, a, b: mtp_join(p, a, b, eps, precision),
                     jp, h, e)
    return vjp(dy)                                      # (d jp, d h, d e)


@jax.jit
def _scatter_rows(table_like, tokens, rows):
    return jnp.zeros_like(table_like).at[tokens.reshape(-1)].add(
        rows.reshape(-1, rows.shape[-1]))


def _flat(tree: dict, i=None) -> dict:
    """A (stacked) tree as a flat dict of float32 leaves (``moe.w1``,
    ``mla.wq_a`` ...); ``i`` picks a layer of a stack."""
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = ".".join(str(k.key) for k in path)
        out[name] = (leaf if i is None else leaf[i]).astype(_F32)
    return out


def _stacks(weights: dict) -> list:
    layers = weights["layers"]
    return [layers] if isinstance(layers, dict) else list(layers)


def _groups(weights: dict):
    """The seed's tree -> (float32 update groups, the table's rows):
    ``embed``, ``head``, one group a layer, and the module's three.  A
    row of the table: (label, [(group, leaf), ...]) as the program's
    stacked leaves reduce."""
    groups = {"embed": {"embed": weights["embed"].astype(_F32)},
              "head": {"ln_f": weights["ln_f"].astype(_F32),
                       "lm_head": weights["lm_head"].astype(_F32)}}
    table = [("embed", [("embed", "embed")]), ("ln_f", [("head", "ln_f")]),
             ("lm_head", [("head", "lm_head")])]
    order = []
    multi = len(_stacks(weights)) > 1
    for s, stack in enumerate(_stacks(weights)):
        count = jax.tree.leaves(stack)[0].shape[0]
        names = [f"layer{len(order) + j}" for j in range(count)]
        for j, name in enumerate(names):
            groups[name] = _flat(stack, j)
        prefix = f"layers.{s}." if multi else "layers."
        table += [(prefix + leaf, [(n, leaf) for n in names])
                  for leaf in groups[names[0]]]
        order += names
    if "mtp" in weights:
        m = weights["mtp"]
        groups["mtp_join"] = {k: m[k].astype(_F32)
                              for k in ("hnorm", "enorm", "w_eh")}
        groups["mtp_layer"] = _flat(m["layers"], 0)
        groups["mtp_head"] = {"ln_f": m["ln_f"].astype(_F32)}
        table += [("mtp." + k, [("mtp_join", k)])
                  for k in ("hnorm", "enorm", "w_eh")]
        table += [("mtp.layers." + leaf, [("mtp_layer", leaf)])
                  for leaf in groups["mtp_layer"]]
        table.append(("mtp.ln_f", [("mtp_head", "ln_f")]))
    return groups, table, order


def _leaf_table(per_group: dict, table) -> dict:
    return {label: np.array([float(per_group[g][leaf]) for g, leaf in where])
            for label, where in table}


def follow(make_weights, batches, cfg: dict, steps: int = 2,
           precision: str = "float32", learning_rate=None, choices=None,
           rotary: bool = True, scoring=None, mtp_coeff=None,
           shared: bool = True) -> dict:
    """Train ``steps`` steps from the seed's weights.  ``batches[t]`` is
    ``[rows, S + 1]`` int tokens.  Returns each step's loss (and its two
    parts), the first gradient's norm per leaf and the norm per leaf of
    the parameters' change over the steps, labelled as the program's
    tree flattens; ``choices`` (per step ``[expert layers, rows, S, k]``,
    the module's layer last), ``routing_gap`` (value, note) and
    ``moe_bias`` ``[expert layers, E]`` after the steps.  ``choices``
    in: the program's, to be followed.  The other keywords are the
    controls'."""
    o = cfg["optimizer"]
    lr = o["learning_rate"] if learning_rate is None else learning_rate
    opt = (lr, o["b1"], o["b2"], o["eps"], o["weight_decay"])
    eps = cfg["rms_norm_eps"]
    coeff = cfg["mtp_loss_coef"] if mtp_coeff is None else mtp_coeff
    gamma = np.float32(cfg["bias_update_rate"])
    n_experts = cfg["n_routed_experts"]
    hp = _static({
        "eps": eps, "theta": float(cfg["rope_theta"]),
        "qk_nope": cfg["qk_nope_head_dim"], "kv_rank": cfg["kv_lora_rank"],
        "top_k": cfg["num_experts_per_tok"],
        "norm_topk": bool(cfg["norm_topk_prob"]),
        "route_scale": float(cfg["routed_scaling_factor"]),
        "scoring": scoring or cfg["scoring_func"],
        "first": cfg["experts_held_first"], "shared": shared,
        "rotary": rotary})

    p, table, order = _groups(make_weights())
    m = jax.tree.map(jnp.zeros_like, p)
    v = jax.tree.map(jnp.zeros_like, p)
    # the layers that route, in the order of the bias's rows
    routed = [n for n in order if "moe.wr" in p[n]] + ["mtp_layer"]
    bias = {n: jnp.zeros((n_experts,), _F32) for n in routed}
    none = jnp.zeros((n_experts,), _F32)
    losses, parts, grad1, used, gaps, worst = [], [], None, [], [], (0.0, "")

    for t in range(1, steps + 1):
        tokens = jnp.asarray(batches[t - 1], jnp.int32)
        inputs, targets = tokens[:, :-1], tokens[:, 1:]
        n_rows, length = inputs.shape
        rows = range(n_rows)
        after_next = jnp.concatenate(
            [targets[:, 1:], jnp.zeros((n_rows, 1), jnp.int32)], axis=1)
        has_one = (jnp.arange(length) < length - 1).astype(_F32)
        every = jnp.ones((length,), _F32)

        def given(name, r):
            if choices is None or name not in routed:
                return None
            return jnp.asarray(choices[t - 1][routed.index(name)][r],
                               jnp.int32)

        step_used = {}

        def forward(name, xs_in):
            outs = jax.block_until_ready(
                [_layer_fwd(p[name], x, bias.get(name, none), given(name, r),
                            hp, precision) for r, x in zip(rows, xs_in)])
            if name in routed:
                step_used[name] = np.stack(
                    [np.asarray(c) for _, (c, _) in outs])
                for r, (_, (_, gap)) in zip(rows, outs):
                    gaps.append(float(gap[0]))
                    nonlocal worst
                    if float(gap[1]) > worst[0]:
                        worst = (float(gap[1]), f"step {t} {name} row {r}")
            return [x for x, _ in outs]

        xs = [[p["embed"]["embed"][inputs[r]] for r in rows]]
        for name in order:
            xs.append(forward(name, xs[-1]))
        h = xs.pop()
        next_rows = [p["embed"]["embed"][targets[r]] for r in rows]
        joined = [_join_fwd(p["mtp_join"], h[r], next_rows[r], eps, precision)
                  for r in rows]
        module_out = forward("mtp_layer", joined)

        # both losses through the shared head
        main, extra, g_head, g_mtp_head, dxs, dzs = 0.0, 0.0, None, None, [], []
        module_head = {"ln_f": p["mtp_head"]["ln_f"],
                       "lm_head": p["head"]["lm_head"]}
        for r in rows:
            l_r, (g_r, dx_r) = _head_vg(p["head"], h[r], targets[r], every,
                                        1.0, eps, n_rows * length, precision)
            e_r, (ge_r, dz_r) = _head_vg(
                module_head, module_out[r], after_next[r], has_one, coeff,
                eps, n_rows * (length - 1), precision)
            main, extra = main + l_r, extra + e_r
            g_r = dict(g_r, lm_head=g_r["lm_head"] + ge_r["lm_head"])
            g_head = g_r if g_head is None else _tree_add(g_head, g_r)
            ge_r = {"ln_f": ge_r["ln_f"]}
            g_mtp_head = ge_r if g_mtp_head is None else _tree_add(
                g_mtp_head, ge_r)
            dxs.append(dx_r)
            dzs.append(dz_r)
        del module_out
        losses.append(float(main + extra))
        parts.append((float(main), float(extra) / coeff if coeff else 0.0))

        norms = {}

        def update(name, g):
            p[name], m[name], v[name], norms[name] = _adamw(
                p[name], m[name], v[name], g, float(t), opt)

        def backward(name, xs_in, dys):
            g_layer = None
            for r in rows:
                g_r, dys[r] = _layer_bwd(
                    p[name], xs_in[r], bias.get(name, none),
                    None if name not in routed else
                    jnp.asarray(step_used[name][r]), dys[r], hp, precision)
                g_layer = g_r if g_layer is None else _tree_add(g_layer, g_r)
            jax.block_until_ready(dys)
            return g_layer

        update("head", g_head)
        update("mtp_head", g_mtp_head)
        update("mtp_layer", backward("mtp_layer", joined, dzs))
        g_join, d_next = None, []
        for r in rows:
            g_r, dh_r, de_r = _join_bwd(p["mtp_join"], h[r], next_rows[r],
                                        dzs[r], eps, precision)
            g_join = g_r if g_join is None else _tree_add(g_join, g_r)
            dxs[r] = dxs[r] + dh_r
            d_next.append(de_r)
        update("mtp_join", g_join)
        del joined, h, next_rows, dzs
        for name in reversed(order):
            update(name, backward(name, xs.pop(), dxs))
        table_like = p["embed"]["embed"]
        update("embed", {"embed": _scatter_rows(
            table_like, inputs, jnp.stack(dxs)) + _scatter_rows(
                table_like, targets, jnp.stack(d_next))})
        if t == 1:
            grad1 = _leaf_table(norms, table)

        # the correction bias, by the experts this step used
        for name in routed:
            load = np.bincount(step_used[name].reshape(-1),
                               minlength=n_experts)[:n_experts]
            under = np.sign(int(load.sum()) - n_experts * load.astype(
                np.int64)).astype(np.float32)
            bias[name] = bias[name] + jnp.asarray(gamma * under)
        used.append(np.stack([step_used[n] for n in routed]))

    del m, v
    start = _groups(make_weights())[0]
    change = {g: {k: _diff_norm(p[g][k], start[g][k]) for k in p[g]}
              for g in p}
    return {"losses": losses, "loss_parts": parts, "grad1_norm": grad1,
            "change_norm": _leaf_table(change, table), "choices": used,
            "moe_bias": np.stack([np.asarray(bias[n]) for n in routed]),
            "routing_gap": (float(np.mean(gaps)), "largest single token "
                            f"{worst[0]:.4g} at {worst[1]}")}
