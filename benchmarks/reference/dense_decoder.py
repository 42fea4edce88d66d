"""Plain reference of a dense decoder's training step: RMSNorm, rotary
multi-head attention, SwiGLU, untied output head, mean next-token cross
entropy, AdamW.  Straightforward ``jax.numpy`` in float32 with every
matrix multiplication at ``highest`` precision; no kernels, no cache, no
batching.  It imports nothing of the program under test and takes
nothing the program made: it is given the configuration, the seed's
weights (made again by ``benchmarks.harness.weights``) and the token
batches.

So that it fits beside nothing else on one 16 GB chip it works layer by
layer and row by row: the forward keeps each layer's input, the backward
takes one layer's ``jax.vjp`` at a time, adds the rows' gradients and
applies AdamW to that layer at once, so no whole-model gradient is ever
held; attention maps over heads so only one ``[S, S]`` score matrix is
alive.  None of that changes a number.

Departures from the published model, mirrored from the program so that
the two compute the same function (listed in the configuration file
under ``assumed``): RMSNorm epsilon and plain (unscaled) RoPE come from
the configuration file, not from the paper.

``precision="fp8"`` is the control: the same code with both operands of
every matrix multiplication rounded to float8_e4m3 and the cotangent
of every product rounded to float8_e5m2 (per-tensor scales, float32
accumulation: an fp8 training step) -- the nearest precision below the
bfloat16 the configuration states.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

_F32 = jnp.float32
_HIGHEST = jax.lax.Precision.HIGHEST
_E4M3_MAX = 448.0
_E5M2_MAX = 57344.0


def _round_fp8(x, dtype, largest):
    """Round to an 8-bit float and back, with one scale for the tensor."""
    scale = largest / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return (x * scale).astype(dtype).astype(_F32) / scale


@jax.custom_vjp
def _round_e4m3(x):
    return _round_fp8(x, jnp.float8_e4m3fn, _E4M3_MAX)


_round_e4m3.defvjp(lambda x: (_round_e4m3(x), None), lambda _, g: (g,))


@jax.custom_vjp
def _round_cotangent_e5m2(y):
    return y


_round_cotangent_e5m2.defvjp(
    lambda y: (y, None),
    lambda _, g: (_round_fp8(g, jnp.float8_e5m2, _E5M2_MAX),))


def _mm(precision: str, eq: str, a, b):
    if precision == "float32":
        return jnp.einsum(eq, a, b, precision=_HIGHEST)
    if precision != "fp8":
        raise ValueError(f"precision {precision!r}")
    # an fp8 training step: e4m3 operands forward, and the backward's
    # two products take the e5m2-rounded cotangent with those operands
    return _round_cotangent_e5m2(jnp.einsum(
        eq, _round_e4m3(a), _round_e4m3(b), precision=_HIGHEST))


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x [S, H, D]; rotate the two halves of each head (the published
    ``rotate_half`` convention), positions 0..S-1."""
    s, _, d = x.shape
    half = d // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=_F32) / half)
    angles = jnp.arange(s, dtype=_F32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attend(q, k, v, precision):
    """Causal softmax attention, [S, H, D] each, one head at a time."""
    s, _, d = q.shape
    causal = jnp.tril(jnp.ones((s, s), bool))

    def one_head(qkv):
        qh, kh, vh = qkv
        scores = _mm(precision, "qd,kd->qk", qh, kh) * (d ** -0.5)
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return _mm(precision, "qk,kd->qd", probs, vh)

    heads = jax.lax.map(jax.checkpoint(one_head),
                        tuple(t.transpose(1, 0, 2) for t in (q, k, v)))
    return heads.transpose(1, 0, 2)


def layer(lp: dict, x, hp: tuple, precision: str):
    """One block on one row ``x [S, d]``."""
    eps, theta = hp
    h = _rms_norm(x, lp["ln1"], eps)
    q = _rope(_mm(precision, "sd,dhk->shk", h, lp["wq"]), theta)
    k = _rope(_mm(precision, "sd,dhk->shk", h, lp["wk"]), theta)
    v = _mm(precision, "sd,dhk->shk", h, lp["wv"])
    x = x + _mm(precision, "shk,hkd->sd", _attend(q, k, v, precision),
                lp["wo"])
    h = _rms_norm(x, lp["ln2"], eps)
    gate = jax.nn.silu(_mm(precision, "sd,df->sf", h, lp["w1"]))
    up = _mm(precision, "sd,df->sf", h, lp["w3"])
    return x + _mm(precision, "sf,fd->sd", gate * up, lp["w2"])


def head_loss(hp_: dict, x, targets, eps, n_tokens, precision):
    """Sum over this row of the cross entropy, over the batch's token
    count: the rows' values add up to the batch mean."""
    logits = _mm(precision, "sd,dv->sv", _rms_norm(x, hp_["ln_f"], eps),
                 hp_["lm_head"])
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
    return jnp.sum(logz - gold) / n_tokens


@functools.partial(jax.jit, static_argnames=("hp", "precision"))
def _layer_fwd(lp, x, hp, precision):
    return layer(lp, x, hp, precision)


@functools.partial(jax.jit, static_argnames=("hp", "precision"))
def _layer_bwd(lp, x, dy, hp, precision):
    _, vjp = jax.vjp(lambda p, a: layer(p, a, hp, precision), lp, x)
    return vjp(dy)                                      # (d lp, d x)


@functools.partial(jax.jit,
                   static_argnames=("eps", "n_tokens", "precision"))
def _head_vg(hp_, x, targets, eps, n_tokens, precision):
    return jax.value_and_grad(
        lambda p, a: head_loss(p, a, targets, eps, n_tokens, precision),
        argnums=(0, 1))(hp_, x)


@jax.jit
def _tree_add(a, b):
    return jax.tree.map(jnp.add, a, b)


@functools.partial(jax.jit, static_argnames=("opt",), donate_argnums=(0, 1, 2))
def _adamw(p, m, v, g, t, opt):
    """One AdamW update of a dict of leaves (optax.adamw's order: Adam
    direction, plus decayed weights, times the learning rate); also the
    gradient's norm per leaf."""
    lr, b1, b2, eps, wd = opt

    def one(p, m, v, g):
        m = b1 * m + (1.0 - b1) * g
        v = b2 * v + (1.0 - b2) * g * g
        m_hat = m / (1.0 - b1 ** t)
        v_hat = v / (1.0 - b2 ** t)
        return (p - lr * (m_hat / (jnp.sqrt(v_hat) + eps) + wd * p), m, v,
                jnp.sqrt(jnp.sum(g * g)))

    out = {k: one(p[k], m[k], v[k], g[k]) for k in p}
    return tuple({k: o[i] for k, o in out.items()} for i in range(4))


@jax.jit
def _embed_grad(tokens, dx, table_shape_like):
    return jnp.zeros_like(table_shape_like).at[tokens.reshape(-1)].add(
        dx.reshape(-1, dx.shape[-1]))


@jax.jit
def _diff_norm(a, b):
    return jnp.sqrt(jnp.sum(jnp.square(a.astype(_F32) - b.astype(_F32))))


def _groups(weights: dict) -> dict:
    """The seed's stacked tree -> float32 update groups: ``embed``,
    ``head`` and one per layer."""
    n_layers = weights["layers"]["wq"].shape[0]
    groups = {"embed": {"embed": weights["embed"].astype(_F32)},
              "head": {"ln_f": weights["ln_f"].astype(_F32),
                       "lm_head": weights["lm_head"].astype(_F32)}}
    for i in range(n_layers):
        groups[f"layer{i}"] = {k: a[i].astype(_F32)
                               for k, a in weights["layers"].items()}
    return groups


def _leaf_table(per_group: dict, n_layers: int) -> dict:
    """{group: {leaf: scalar}} -> {leaf: [n_layers] or [1]} in numpy, the
    shape the program's stacked leaves reduce to."""
    out = {"embed": np.array([float(per_group["embed"]["embed"])]),
           "ln_f": np.array([float(per_group["head"]["ln_f"])]),
           "lm_head": np.array([float(per_group["head"]["lm_head"])])}
    for leaf in per_group["layer0"]:
        out["layers." + leaf] = np.array(
            [float(per_group[f"layer{i}"][leaf]) for i in range(n_layers)])
    return out


def follow(make_weights, batches, cfg: dict, steps: int = 3,
           precision: str = "float32", batch_rows=None,
           learning_rate=None) -> dict:
    """Train ``steps`` steps from the seed's weights on ``batches``
    (``[steps, B, S + 1]`` int tokens).  Returns each step's loss, the
    first gradient's norm per leaf, and the norm per leaf of the
    parameters' change over the steps.

    ``make_weights()`` returns the seed's tree; it is called again at
    the end for the starting point, so that no second copy is held.
    ``batch_rows`` (fault injection for the tests and the controls)
    restricts the step to those rows of each batch, the mean taken over
    them; ``learning_rate=0.0`` is a step that returns its parameters
    unchanged.

    The host waits for the device after every layer: buffers are taken
    when a call is enqueued, and a host that runs a whole step ahead
    holds every layer's temporaries at once."""
    o = cfg["optimizer"]
    lr = o["learning_rate"] if learning_rate is None else learning_rate
    opt = (lr, o["b1"], o["b2"], o["eps"], o["weight_decay"])
    hp = (cfg["rms_norm_eps"], cfg["rope_theta"])
    eps = cfg["rms_norm_eps"]
    n_layers = cfg["num_hidden_layers"]

    p = _groups(make_weights())
    m = jax.tree.map(jnp.zeros_like, p)
    v = jax.tree.map(jnp.zeros_like, p)
    losses, grad1 = [], None

    for t in range(1, steps + 1):
        tokens = jnp.asarray(batches[t - 1], jnp.int32)
        if batch_rows is not None:
            tokens = tokens[jnp.asarray(batch_rows)]
        inputs, targets = tokens[:, :-1], tokens[:, 1:]
        rows = range(tokens.shape[0])
        n_tokens = int(inputs.size)

        xs = [[p["embed"]["embed"][inputs[r]] for r in rows]]
        for i in range(n_layers):
            xs.append(jax.block_until_ready(
                [_layer_fwd(p[f"layer{i}"], x, hp, precision)
                 for x in xs[-1]]))

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
            p[name], m[name], v[name], norms[name] = _adamw(
                p[name], m[name], v[name], g, float(t), opt)

        update("head", g_head)
        for i in reversed(range(n_layers)):
            g_layer, x_in = None, xs.pop()
            for r in rows:
                g_r, dxs[r] = _layer_bwd(p[f"layer{i}"], x_in[r], dxs[r],
                                         hp, precision)
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
            "change_norm": _leaf_table(change, n_layers)}
