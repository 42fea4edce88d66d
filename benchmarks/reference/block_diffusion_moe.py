"""Plain reference of a block-diffusion training step of a sparse-expert
decoder, on one expert-parallel rank's share: RMSNorm, rotary attention
with grouped K/V heads and per-head RMSNorm of q and k, a router over
all experts with the top-k renormalised, the SwiGLU experts held here,
an untied output head over the vocabulary slice, the BD3-LM loss, AdamW.
Straightforward ``jax.numpy`` in float32 with every matrix
multiplication at ``highest`` precision; no kernels, no sorting, no
batching.  It imports nothing of the program under test (the helpers it
shares with ``dense_decoder.py`` are the benchmark's own).

The layer, for one row ``x [2L, d]`` = ``[noisy ; clean]`` with
positions ``[0..L) ; [0..L)`` and ``b(i) = (i mod L) // B``:

    h   = rmsnorm(x; ln1)
    q   = rope(rmsnorm_head(h Wq; q_norm))     k likewise, v = h Wv
    query head j reads K/V head j // group
    a noisy row sees the noisy keys of its own block and the clean keys
    of blocks before it; a clean row the clean keys of blocks up to its
    own; softmax over what it sees, scale 1/sqrt(head_dim)
    x   = x + o Wo
    h   = rmsnorm(x; ln2);  p = softmax(h Wr);  S = the k largest of p
    g_e = p_e / sum_{S} p   (norm_topk_prob)  for e in S, else 0
    x   = x + sum over held e of g_e (silu(h W1_e) * (h W3_e)) W2_e

    loss = 1/(rows L) sum_{i < L, masked} weight_i (logsumexp(z_i) - z_i[x0_i])

Only the three non-empty quadrants of the mask are computed, and each
held expert runs over every position with its gate (nought where it was
not chosen): both are the published arithmetic.  What absent experts
would add is left out, as in the program.

So that it fits beside nothing else on one 16 GB chip it works layer by
layer and row by row like ``dense_decoder.follow``.

Routing.  A token whose k-th and (k+1)-th expert lie within the
program's rounding of each other takes another expert in float32, and a
different expert moves the experts' and the router's gradients by far
more than any rounding does.  So ``follow`` can be handed the experts
the program chose (``choices``): it computes with those, its own
probabilities as gates, and holds every choice to its own
probabilities: a token falls short by log(its own k-th largest
probability) - log(the smallest probability among the token's given
experts): 0 where the given experts are its own top k, the size of the
tie where two swapped, large for a router that chose by something else.
``routing_gap`` is the mean of that over all tokens, layers and steps
(infinite where a token was given a repeated or absent expert); the
largest single token is named beside it.  The mean grows with the
square of the program's rounding (more tokens swap, each by more),
which is what tells one precision from the next.
Without ``choices`` it routes by itself and returns what it chose.

Controls: ``precision="fp8"`` (operands e4m3, cotangents e5m2: the
nearest precision below the configuration's bfloat16) and
``mask="causal"`` (plain causal attention over the 2L positions in
place of the block-diffusion mask).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference.dense_decoder import (_adamw, _attend, _diff_norm,
                                                _embed_grad, _leaf_table, _mm,
                                                _rms_norm, _tree_add)

_F32 = jnp.float32


def _rope(x, positions, theta):
    """x [S, H, D]; rotate the two halves of each head."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=_F32) / half)
    angles = positions.astype(_F32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attend_block_diffusion(q, k, v, length, block, precision):
    """q, k, v [2L, H, D] (K/V already one per query head), one head at
    a time; the clean-to-noisy quadrant is empty and never formed."""
    d = q.shape[-1]
    scale = d ** -0.5
    nb = length // block
    blk = jnp.arange(length) // block
    before = blk[None, :] < blk[:, None]           # clean key j, noisy row i
    upto = blk[None, :] <= blk[:, None]            # clean key j, clean row i

    def one_head(qkv):
        qh, kh, vh = qkv
        qn, qc = qh[:length], qh[length:]
        kn, kc = kh[:length], kh[length:]
        vn, vc = vh[:length], vh[length:]
        own = _mm(precision, "nbd,ncd->nbc", qn.reshape(nb, block, d),
                  kn.reshape(nb, block, d)) * scale
        past = _mm(precision, "qd,kd->qk", qn, kc) * scale
        probs = jax.nn.softmax(jnp.concatenate(
            [own.reshape(length, block),
             jnp.where(before, past, -jnp.inf)], axis=-1), axis=-1)
        out_n = _mm(precision, "nbc,ncd->nbd",
                    probs[:, :block].reshape(nb, block, block),
                    vn.reshape(nb, block, d)).reshape(length, d) \
            + _mm(precision, "qk,kd->qd", probs[:, block:], vc)
        clean = _mm(precision, "qd,kd->qk", qc, kc) * scale
        out_c = _mm(precision, "qk,kd->qd", jax.nn.softmax(
            jnp.where(upto, clean, -jnp.inf), axis=-1), vc)
        return jnp.concatenate([out_n, out_c], axis=0)

    heads = jax.lax.map(jax.checkpoint(one_head),
                        tuple(t.transpose(1, 0, 2) for t in (q, k, v)))
    return heads.transpose(1, 0, 2)


def _experts(lp, h, hp, precision, given):
    """The held experts' part of the expert layer on ``h [S, d]`` ->
    (y, the experts used [S, k], this row's routing gap as (mean,
    largest)).  ``given``: the experts to use, or None to route by
    itself."""
    top_k, norm_topk, first = hp["top_k"], hp["norm_topk"], hp["first"]
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
    if norm_topk:
        gate = gate / jnp.sum(gate, axis=-1, keepdims=True)

    def one_expert(y, ew):
        e, w1, w3, w2 = ew
        g = jnp.sum(jnp.where(chosen == first + e, gate, 0.0), axis=-1)
        act = jax.nn.silu(_mm(precision, "sd,df->sf", h, w1)) * \
            _mm(precision, "sd,df->sf", h, w3)
        return y + g[:, None] * _mm(precision, "sf,fd->sd", act, w2), None

    held = lp["moe.w1"].shape[0]
    y, _ = jax.lax.scan(jax.checkpoint(one_expert), jnp.zeros_like(h),
                        (jnp.arange(held), lp["moe.w1"], lp["moe.w3"],
                         lp["moe.w2"]))
    return y, chosen, gap


def layer(lp: dict, x, hp: dict, precision: str, mask: str, given=None):
    """One block on one row ``x [2L, d]`` -> (x, (experts used, routing
    gap))."""
    eps, theta, length = hp["eps"], hp["theta"], hp["length"]
    half = jnp.arange(length)
    positions = jnp.concatenate([half, half])
    h = _rms_norm(x, lp["ln1"], eps)
    q = _mm(precision, "sd,dhk->shk", h, lp["wq"])
    k = _mm(precision, "sd,dhk->shk", h, lp["wk"])
    v = _mm(precision, "sd,dhk->shk", h, lp["wv"])
    q = _rope(_rms_norm(q, lp["q_norm"], eps), positions, theta)
    k = _rope(_rms_norm(k, lp["k_norm"], eps), positions, theta)
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    if mask == "block_diffusion":
        o = _attend_block_diffusion(q, k, v, length, hp["block"], precision)
    elif mask == "causal":
        # the control: plain causal attention over all 2L positions
        o = _attend(q, k, v, precision)
    else:
        raise ValueError(f"mask {mask!r}")
    x = x + _mm(precision, "shk,hkd->sd", o, lp["wo"])
    y, chosen, gap = _experts(lp, _rms_norm(x, lp["ln2"], eps), hp,
                              precision, given)
    return x + y, (chosen, gap)


def head_loss(hp_: dict, x, targets, weight, eps, n_tokens, precision):
    """This row's part of the loss: ``x [L, d]`` is the noisy half."""
    logits = _mm(precision, "sd,dv->sv", _rms_norm(x, hp_["ln_f"], eps),
                 hp_["lm_head"])
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
    return jnp.sum(weight * (logz - gold)) / n_tokens


def _static(hp):
    return tuple(sorted(hp.items()))


@functools.partial(jax.jit, static_argnames=("hp", "precision", "mask"))
def _layer_fwd(lp, x, given, hp, precision, mask):
    return layer(lp, x, dict(hp), precision, mask, given)


@functools.partial(jax.jit, static_argnames=("hp", "precision", "mask"))
def _layer_bwd(lp, x, given, dy, hp, precision, mask):
    """``given``: the experts the forward used (its own or the
    program's), so both passes route alike."""
    _, vjp, _ = jax.vjp(
        lambda p, a: layer(p, a, dict(hp), precision, mask, given),
        lp, x, has_aux=True)
    return vjp(dy)                                      # (d lp, d x)


@functools.partial(jax.jit,
                   static_argnames=("eps", "n_tokens", "precision"))
def _head_vg(hp_, x, targets, weight, eps, n_tokens, precision):
    return jax.value_and_grad(
        lambda p, a: head_loss(p, a, targets, weight, eps, n_tokens,
                               precision), argnums=(0, 1))(hp_, x)


def _layer_leaves(layers: dict, i: int) -> dict:
    """Layer ``i`` of the stacked tree as a flat dict (``moe.w1``...)."""
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(layers)[0]:
        out[".".join(str(k.key) for k in path)] = leaf[i].astype(_F32)
    return out


def _groups(weights: dict) -> dict:
    """The seed's stacked tree -> float32 update groups: ``embed``,
    ``head`` and one per layer."""
    n_layers = weights["layers"]["wq"].shape[0]
    groups = {"embed": {"embed": weights["embed"].astype(_F32)},
              "head": {"ln_f": weights["ln_f"].astype(_F32),
                       "lm_head": weights["lm_head"].astype(_F32)}}
    for i in range(n_layers):
        groups[f"layer{i}"] = _layer_leaves(weights["layers"], i)
    return groups


def follow(make_weights, batches, cfg: dict, steps: int = 2,
           precision: str = "float32", mask: str = "block_diffusion",
           learning_rate=None, choices=None) -> dict:
    """Train ``steps`` steps from the seed's weights.  ``batches[t]`` is
    ``{"tokens", "noisy", "weight"}``, each ``[rows, L]`` (the clean
    tokens, the noised ones and the loss weights, drawn by the caller).
    Returns each step's loss, the first gradient's norm per leaf and the
    norm per leaf of the parameters' change over the steps, as
    ``dense_decoder.follow`` does, and ``choices`` (per step ``[layers,
    rows, 2L, k]``, the experts used) with ``routing_gap`` (value,
    note): see "Routing" above.  ``choices`` in: the program's, to be
    followed.  ``precision``, ``mask`` and ``learning_rate`` are the
    controls'."""
    o = cfg["optimizer"]
    lr = o["learning_rate"] if learning_rate is None else learning_rate
    opt = (lr, o["b1"], o["b2"], o["eps"], o["weight_decay"])
    eps = cfg["rms_norm_eps"]
    n_layers = cfg["num_hidden_layers"]

    p = _groups(make_weights())
    m = jax.tree.map(jnp.zeros_like, p)
    v = jax.tree.map(jnp.zeros_like, p)
    losses, grad1, used, gaps, worst = [], None, [], [], (0.0, "")

    for t in range(1, steps + 1):
        batch = batches[t - 1]
        x0 = jnp.asarray(batch["tokens"], jnp.int32)
        xt = jnp.asarray(batch["noisy"], jnp.int32)
        weight = jnp.asarray(batch["weight"], _F32)
        n_rows, length = x0.shape
        rows = range(n_rows)
        n_tokens = int(x0.size)
        inputs = jnp.concatenate([xt, x0], axis=1)             # [rows, 2L]
        hp = _static({
            "eps": eps, "theta": float(cfg["rope_theta"]), "length": length,
            "block": cfg["block_diffusion"]["block_length"],
            "top_k": cfg["num_experts_per_tok"],
            "norm_topk": bool(cfg["norm_topk_prob"]),
            "first": cfg["experts_held_first"]})

        xs = [[p["embed"]["embed"][inputs[r]] for r in rows]]
        used.append([])
        for i in range(n_layers):
            outs = jax.block_until_ready(
                [_layer_fwd(p[f"layer{i}"], x,
                            None if choices is None else
                            jnp.asarray(choices[t - 1][i][r], jnp.int32),
                            hp, precision, mask)
                 for r, x in zip(rows, xs[-1])])
            xs.append([x for x, _ in outs])
            used[-1].append(np.stack([np.asarray(c) for _, (c, _) in outs]))
            for r, (_, (_, gap)) in zip(rows, outs):
                gaps.append(float(gap[0]))
                if float(gap[1]) > worst[0]:
                    worst = (float(gap[1]), f"step {t} layer {i} row {r}")

        loss, g_head, dxs = 0.0, None, []
        for r in rows:
            l_r, (g_r, dx_r) = _head_vg(p["head"], xs[-1][r][:length], x0[r],
                                        weight[r], eps, n_tokens, precision)
            loss = loss + l_r
            g_head = g_r if g_head is None else _tree_add(g_head, g_r)
            # the clean half reaches the loss through attention only
            dxs.append(jnp.concatenate([dx_r, jnp.zeros_like(dx_r)], axis=0))
        xs.pop()
        losses.append(float(loss))

        norms = {}

        def update(name, g):
            p[name], m[name], v[name], norms[name] = _adamw(
                p[name], m[name], v[name], g, float(t), opt)

        update("head", g_head)
        for i in reversed(range(n_layers)):
            g_layer, x_in = None, xs.pop()
            for r in rows:
                g_r, dxs[r] = _layer_bwd(
                    p[f"layer{i}"], x_in[r], jnp.asarray(used[-1][i][r]),
                    dxs[r], hp, precision, mask)
                g_layer = g_r if g_layer is None else _tree_add(g_layer, g_r)
            update(f"layer{i}", g_layer)
            jax.block_until_ready(dxs)
        update("embed", {"embed": _embed_grad(
            inputs, jnp.stack(dxs), p["embed"]["embed"])})
        if t == 1:
            grad1 = _leaf_table(norms, n_layers)

    del m, v
    start = _groups(make_weights())
    change = {g: {k: _diff_norm(p[g][k], start[g][k]) for k in p[g]}
              for g in p}
    return {"losses": losses, "grad1_norm": grad1,
            "change_norm": _leaf_table(change, n_layers),
            "choices": [np.stack(layers) for layers in used],
            "routing_gap": (float(np.mean(gaps)), "largest single token "
                            f"{worst[0]:.4g} at {worst[1]}")}
