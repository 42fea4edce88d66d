"""Plain reference of a training step of a slice of a decoder-hybrid-
decoder (Phi-4-mini-flash-reasoning's layers, as ``modeling_phi4flash.py``
and arXiv:2507.06607 describe them): Mamba-1 selective state-space
layers, differential attention under a window or over everything before,
Gated Memory Units that gate one Mamba layer's scan output, differential
cross attention over one layer's keys and values; a SwiGLU in every
layer, LayerNorm (weight and bias) before each half, no positional
encoding, the head tied to the embedding.  Straightforward ``jax.numpy``
in float32 with every matrix multiplication at ``highest`` precision; no
kernels, no chunks.  It imports nothing of the program under test (the
helpers it shares with the other references, and ``layer_plan``, are the
benchmark's own).  No departure from the published description is
known; the published configuration has no dropout.

For one row ``x [S, d]``; ``u = LN1(x)``; every layer is ``x <- x +
mixer(u)``, then ``x <- x + (silu(LN2(x) W1) . LN2(x) W3) W2``:

  Mamba (E channels, N states, R the step size's rank):
    s | z = u W_in;  c = silu(sum_j taps_j . s_{t-3+j} + b_conv)
    dt | B | C = c W_x;  delta = softplus(dt W_dt + b_dt);  A = -exp(A_log)
    TOKEN BY TOKEN from h = 0 at the row's start:
        h_t = exp(delta_t (x) 1 . A) . h_{t-1} + (delta_t . c_t) (x) B_t
        y_t = h_t C_t + D . c_t
    mixer = (y . silu(z)) W_out;  the memory M = y (before the gate)
  Gated Memory Unit:  mixer = (M . silu(u W_in)) W_out
  differential attention (H query heads, K K/V heads of Dh, biases):
    q | k | v = u W + b;  q1_i, q2_i = query heads 2i, 2i + 1;  k1_j, k2_j
    = K heads 2j, 2j + 1;  V_j = [v_2j | v_2j+1];  pair i reads pair
    j = i // (H / K);  a^s_i = softmax_mask(q^s_i k^s_j^T Dh^-1/2) V_j
    lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init,
    lambda_init = 0.8 - 0.6 exp(-0.3 l), l the layer's published index
    o_i = (1 - lambda_init) rmsnorm(a^1_i - lambda a^2_i; subln, 1e-5)
    mixer = [o_i] W_o + b_o
    the mask: causal, and under ``window`` a query sees its own position
    and the window - 1 before it; a cross layer projects q alone and
    attends over the handed keys and values, causally
  head: logits = LN_f(x) E^T, E the embedding; mean cross entropy

The recurrence is a ``lax.scan`` over positions (in blocks of 64 only so
that its backward keeps one state a block, not one a position: no number
changes); attention is a masked softmax a head and a block of 2,048
queries at a time.  Layers run one by one, forward then backward; what a
layer hands on is an output of its function and an input of its
readers', so the readers' cotangents are summed into the writer's.  Both
Adam moments wait on the host between a group's updates (5.6 GB at the
cell's size): the chip holds the float32 weights and one layer's working
set at 16,384 positions.

The rule alone.  At the published initialisation ``B`` and ``C`` are
small and the ``D`` skip carries ``y``: a gap of gradient norms does not
see what the state is kept in.  So ``follow`` also runs the recurrence
alone, forward AND backward, on seeded inputs of the step's own shape
whose ``delta . A`` spans 1e-4 to 16 a position (``rule_probe_inputs``):
``rule_probe`` is its output and, by ``jax.vjp``, the gradients of c,
delta, A, B and C.  ``rule_gaps`` holds the program's scan to it by the
worst channel's norm of the DIFFERENCE over the norm: ``ssm_rule_gap``
over the output, ``ssm_rule_grad_gap`` over the five gradients (dB and
dC, which have no channel, whole).

Controls (``follow``'s keywords), each the same code with one thing
changed: ``precision="fp8"``; ``state="bfloat16"`` (the state rounded to
bfloat16 after every position); ``window`` (1024, or 0: none);
``lam="init"`` (lambda held at lambda_init); ``subln=False``;
``cross_kv="own"`` (the cross layer's keys and values from its own
input through the writer's matrices); ``memory_from`` (the published
index of another Mamba layer whose output the unit gates);
``kv_cotangent=False`` (the cross layer's cotangent into the keys and
values stopped).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.harness.sambay_weights import layer_plan
from benchmarks.harness.weights import seed_key
from benchmarks.reference.dense_decoder import (_adamw, _diff_norm,
                                                _embed_grad, _mm, _tree_add)
from benchmarks.reference.gdn_gated_moe import _rounded, _shifted
from benchmarks.reference.mla_moe_mtp import _leaf_table, _static, _swiglu

_F32 = jnp.float32
_SUBLN_EPS = 1e-5
_SCAN_BLOCK = 64
_QUERY_BLOCK = 2048


def _layer_norm(x, w, b, eps):
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w + b


def selective_recurrence(c, delta, a, b, cc, state_dtype=None):
    """c, delta [S, E], a [E, N], b, cc [S, N] -> y [S, E] without the
    ``D`` skip: the state token by token, float32 (rounded to
    ``state_dtype`` after every position where given)."""
    length = c.shape[0]
    block = _SCAN_BLOCK if length % _SCAN_BLOCK == 0 else length

    def step(h, at):
        c_t, dt_t, b_t, c_out = at
        h = jnp.exp(dt_t[:, None] * a) * h + (dt_t * c_t)[:, None] * b_t[None]
        if state_dtype is not None:
            h = _rounded(h, state_dtype)
        return h, jnp.sum(h * c_out[None], axis=-1)

    @jax.checkpoint
    def one_block(h, at):
        return jax.lax.scan(step, h, at)

    _, y = jax.lax.scan(
        one_block, jnp.zeros(a.shape, _F32),
        tuple(x.reshape(length // block, block, x.shape[-1])
              for x in (c, delta, b, cc)))
    return y.reshape(length, -1)


def _attend(q, k, v, scale, window, precision):
    """q, k [S, H, Dk], v [S, H, Dv]: masked softmax attention, one head
    and one block of queries at a time.  ``window`` None: causal."""
    s = q.shape[0]
    block = _QUERY_BLOCK if s % _QUERY_BLOCK == 0 else s
    k_pos = jnp.arange(s)

    def one_head(qkv):
        qh, kh, vh = qkv

        def one_block(at):
            qb, start = at
            q_pos = start + jnp.arange(block)
            allowed = q_pos[:, None] >= k_pos[None]
            if window is not None:
                allowed &= q_pos[:, None] - k_pos[None] < window
            scores = _mm(precision, "qd,kd->qk", qb, kh) * scale
            probs = jax.nn.softmax(jnp.where(allowed, scores, -jnp.inf),
                                   axis=-1)
            return _mm(precision, "qk,kd->qd", probs, vh)

        out = jax.lax.map(jax.checkpoint(one_block),
                          (qh.reshape(s // block, block, -1),
                           jnp.arange(s // block) * block))
        return out.reshape(s, -1)

    heads = jax.lax.map(jax.checkpoint(one_head),
                        tuple(t.transpose(1, 0, 2) for t in (q, k, v)))
    return heads.transpose(1, 0, 2)


def _mamba(lp, u, hp, precision):
    """-> (what the layer adds, its scan output before the gate)."""
    e, n, r = hp["d_inner"], hp["d_state"], hp["dt_rank"]
    sz = _mm(precision, "sd,de->se", u, lp["mamba.w_in"])
    s, z = sz[:, :e], sz[:, e:]
    taps = lp["mamba.conv"]
    k = taps.shape[-1]
    c = jax.nn.silu(sum(_shifted(s, k - 1 - j) * taps[:, j]
                        for j in range(k)) + lp["mamba.conv_b"])
    dbc = _mm(precision, "se,er->sr", c, lp["mamba.w_x"])
    delta = jax.nn.softplus(
        _mm(precision, "sr,re->se", dbc[:, :r], lp["mamba.w_dt"])
        + lp["mamba.dt_b"])
    y = selective_recurrence(
        c, delta, -jnp.exp(lp["mamba.A_log"]), dbc[:, r:r + n],
        dbc[:, r + n:],
        jnp.bfloat16 if hp["state"] == "bfloat16" else None)
    y = y + lp["mamba.D"] * c
    return _mm(precision, "se,ed->sd", y * jax.nn.silu(z),
               lp["mamba.w_out"]), y


def _gmu(lp, u, memory, precision):
    gate = jax.nn.silu(_mm(precision, "sd,de->se", u, lp["gmu.w_in"]))
    return _mm(precision, "se,ed->sd", memory * gate, lp["gmu.w_out"])


def _keys_values(w, u, precision):
    """The K/V projections ``w = (wk, bk, wv, bv)`` on ``u`` -> (k1, k2
    [S, K / 2, Dh], V [S, K / 2, 2 Dh])."""
    wk, bk, wv, bv = w
    k = _mm(precision, "sd,dhk->shk", u, wk) + bk
    v = _mm(precision, "sd,dhk->shk", u, wv) + bv
    s, kv, dh = v.shape
    return k[:, 0::2], k[:, 1::2], v.reshape(s, kv // 2, 2 * dh)


def _diff(lp, u, kv, index, window, hp, precision):
    """-> (what the layer adds, the keys and values it attended over)."""
    q = _mm(precision, "sd,dhk->shk", u, lp["diff.wq"]) + lp["diff.bq"]
    q1, q2 = q[:, 0::2], q[:, 1::2]
    if kv is None:
        kv = _keys_values((lp["diff.wk"], lp["diff.bk"], lp["diff.wv"],
                           lp["diff.bv"]), u, precision)
    k1, k2, v = kv
    group = q1.shape[1] // k1.shape[1]
    scale = q.shape[-1] ** -0.5

    def attend(qs, ks):
        return _attend(qs, jnp.repeat(ks, group, axis=1),
                       jnp.repeat(v, group, axis=1), scale, window, precision)

    first = 0.8 - 0.6 * math.exp(-0.3 * index)
    lam = first
    if hp["lam"] != "init":
        lam = (jnp.exp(jnp.sum(lp["diff.lambda_q1"] * lp["diff.lambda_k1"]))
               - jnp.exp(jnp.sum(lp["diff.lambda_q2"] * lp["diff.lambda_k2"]))
               + first)
    o = attend(q1, k1) - lam * attend(q2, k2)
    if hp["subln"]:
        o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                              + _SUBLN_EPS) * lp["diff.subln"]
    o = o * (1.0 - first)
    return _mm(precision, "shk,hkd->sd", o, lp["diff.wo"]) + lp["diff.bo"], kv


def layer(lp: dict, x, handed, spec: dict, hp: dict, precision: str):
    """One block on one row ``x [S, d]`` -> (x, what the layer hands on
    or None).  ``spec``: the layer's entry of ``layer_plan``; ``handed``:
    what it reads (the memory; the keys and values; under ``cross_kv=
    "own"`` the writer's K/V matrices)."""
    eps = hp["eps"]
    u = _layer_norm(x, lp["ln1"], lp["ln1_b"], eps)
    out = None
    if spec["kind"] == "mamba":
        y, out = _mamba(lp, u, hp, precision)
    elif spec["kind"] == "gmu":
        y = _gmu(lp, u, handed, precision)
    else:
        kv = handed
        if spec["reads"] and hp["cross_kv"] == "own":
            kv = _keys_values(jax.lax.stop_gradient(handed), u, precision)
        elif spec["reads"] and not hp["kv_cotangent"]:
            kv = jax.lax.stop_gradient(handed)
        y, out = _diff(lp, u, kv, spec["index"], spec["window"], hp,
                       precision)
    x = x + y
    h = _layer_norm(x, lp["ln2"], lp["ln2_b"], eps)
    return x + _swiglu(h, lp["w1"], lp["w3"], lp["w2"], precision), out


def head_loss(hp_: dict, x, targets, eps, n_tokens, precision):
    logits = _mm(precision, "sd,vd->sv",
                 _layer_norm(x, hp_["ln_f"], hp_["ln_f_b"], eps),
                 hp_["embed"])
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
    return jnp.sum(logz - gold) / n_tokens


@functools.partial(jax.jit, static_argnames=("spec", "hp", "precision"))
def _layer_fwd(lp, x, handed, spec, hp, precision):
    return layer(lp, x, handed, dict(spec), dict(hp), precision)


@functools.partial(jax.jit, static_argnames=("spec", "hp", "precision"))
def _layer_bwd(lp, x, handed, dy, dout, spec, hp, precision):
    """-> (d lp, d x, d handed)."""
    (_, out), vjp = jax.vjp(
        lambda p, a, s: layer(p, a, s, dict(spec), dict(hp), precision),
        lp, x, handed)
    if dout is None:                       # nobody read what it handed on
        dout = jax.tree.map(jnp.zeros_like, out)
    return vjp((dy, dout))


@functools.partial(jax.jit,
                   static_argnames=("eps", "n_tokens", "precision"))
def _head_vg(hp_, x, targets, eps, n_tokens, precision):
    return jax.value_and_grad(
        lambda p, a: head_loss(p, a, targets, eps, n_tokens, precision),
        argnums=(0, 1))(hp_, x)


# ---- the rule alone ------------------------------------------------------

PROBE_PARTS = ("y", "dc", "ddelta", "dA", "dB", "dC")


@functools.partial(jax.jit, static_argnames=("shape", "dtype"))
def _probe_draw(key, shape, dtype):
    rows, length, e, n = shape
    kc, kd, kb, kcc, ko = jax.random.split(key, 5)
    # delta . A: a channel's rate times its state's, 1e-4 to 16 in all
    rate = jnp.exp(jnp.linspace(math.log(1e-4), math.log(1.0), e))
    a = -jnp.broadcast_to(jnp.arange(1, n + 1, dtype=_F32), (e, n))
    delta = rate * jax.nn.softplus(
        jax.random.normal(kd, (rows, length, e), _F32) + 1.0) / 1.5
    c, dy = (jax.random.normal(k, (rows, length, e), _F32).astype(dtype)
             .astype(_F32) for k in (kc, ko))
    b, cc = (jax.random.normal(k, (rows, length, n), _F32)
             for k in (kb, kcc))
    return c, delta, a, b, cc, dy


def rule_probe_inputs(seed: int, cfg: dict, rows: int, length: int):
    """(c, delta, A, B, C, dy) for ``rows`` rows of ``length`` positions
    at the configuration's channels and states, float32, drawn on the
    device from the seed: c and the output's cotangent ~ N(0, 1) rounded
    to the configuration's type, B, C ~ N(0, 1), ``A[e, n] = -(n + 1)``
    (the initialisation's) and ``delta = rate_e softplus(N(0, 1) + 1) /
    1.5`` with the channels' rates spaced evenly in the logarithm from
    1e-4 to 1: ``delta . A`` from 1e-4 (a state that remembers the whole
    row) to 16 (one that forgets within a position)."""
    key = jax.random.fold_in(seed_key(seed),
                             int.from_bytes(b"scan", "little"))
    return _probe_draw(key, (rows, length, cfg["mamba_d_inner"],
                             cfg["mamba_d_state"]), jnp.dtype(cfg["dtype"]))


@functools.partial(jax.jit, static_argnames=("state",))
def _rule_probe_row(c, delta, a, b, cc, dy, state):
    y, vjp = jax.vjp(
        lambda *x: selective_recurrence(
            *x, jnp.bfloat16 if state == "bfloat16" else None),
        c, delta, a, b, cc)
    return (y, *vjp(dy))


def rule_probe(inputs, state: str = "float32") -> dict:
    """The recurrence and its ``jax.vjp`` on a probe's ``inputs``, a row
    at a time -> ``PROBE_PARTS`` as float32 arrays on the host (``dA``
    summed over the rows)."""
    c, delta, a, b, cc, dy = inputs
    rows = [_rule_probe_row(c[r], delta[r], a, b[r], cc[r], dy[r], state)
            for r in range(c.shape[0])]
    out = {name: np.stack([np.asarray(row[i]) for row in rows])
           for i, name in enumerate(PROBE_PARTS)}
    out["dA"] = out["dA"].sum(axis=0)
    return out


def _channel_gaps(prog, ref, axis):
    """``|prog - ref| / |ref|`` a channel (``axis``; None: whole)."""
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
    """Two probes (``PROBE_PARTS``) -> ``ssm_rule_gap``, the worst
    channel's ``|prog - ref| / |ref|`` of the output, and
    ``ssm_rule_grad_gap``, the worst channel's of the five gradients,
    each (gap, which part and channel)."""
    axes = {"y": 2, "dc": 2, "ddelta": 2, "dA": 0, "dB": None, "dC": None}
    worst = {}
    for name in PROBE_PARTS:
        if prog[name].shape != ref[name].shape:
            worst[name] = (math.inf, f"{name}: {prog[name].shape} against "
                           f"{ref[name].shape}")
            continue
        gap, at, of = _channel_gaps(prog[name], ref[name], axes[name])
        worst[name] = (gap, f"{name}, channel {at} of {of}")
    return {"ssm_rule_gap": worst["y"],
            "ssm_rule_grad_gap": max((worst[name] for name in
                                      PROBE_PARTS[1:]), key=lambda w: w[0])}


# ---- the steps -----------------------------------------------------------

def _flat(tree: dict) -> dict:
    """A layer's stack ``[1, ...]`` as a flat dict of float32 leaves
    (``mamba.w_in``, ``w1`` ...)."""
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        out[".".join(str(k.key) for k in path)] = leaf[0].astype(_F32)
    return out


def _groups(weights: dict):
    """The seed's tree -> (float32 update groups, the table's rows, the
    layers' names in order).  The embedding is one group (its gradient
    is the head's and the lookup's together), the final norm another."""
    groups = {"embed": {"embed": weights["embed"].astype(_F32)},
              "final": {"ln_f": weights["ln_f"].astype(_F32),
                        "ln_f_b": weights["ln_f_b"].astype(_F32)}}
    table = [("embed", [("embed", "embed")]), ("ln_f", [("final", "ln_f")]),
             ("ln_f_b", [("final", "ln_f_b")])]
    order = []
    for i, stack in enumerate(weights["layers"]):
        name = f"layer{i}"
        groups[name] = _flat(stack)
        order.append(name)
        table += [(f"layers.{i}.{leaf}", [(name, leaf)])
                  for leaf in groups[name]]
    return groups, table, order


def follow(make_weights, batches, cfg: dict, steps: int = 2,
           precision: str = "float32", learning_rate=None,
           state: str = "float32", window=None, lam: str = "learned",
           subln: bool = True, cross_kv: str = "handed", memory_from=None,
           kv_cotangent: bool = True, probe_seed: int = 0) -> dict:
    """Train ``steps`` steps from the seed's weights.  ``batches[t]`` is
    ``[rows, S + 1]`` int tokens.  Returns each step's loss, the first
    gradient's norm per leaf and the norm per leaf of the parameters'
    change over the steps, labelled as the program's tree flattens, and
    ``rule_probe`` (the recurrence alone with its ``jax.vjp`` on the
    seed's probe at the step's shape: ``PROBE_PARTS``).  The keywords
    after ``learning_rate`` are the controls'."""
    o = cfg["optimizer"]
    lr = o["learning_rate"] if learning_rate is None else learning_rate
    opt = (lr, o["b1"], o["b2"], o["eps"], o["weight_decay"])
    eps = cfg["layer_norm_eps"]
    plan = layer_plan(cfg)
    if window is not None:
        for entry in plan:
            if entry["window"] is not None:
                entry["window"] = window or None
    memory_at = next((i for i, e in enumerate(plan)
                      if e["index"] == memory_from), None)
    # a layer is handed the controls of its own kind alone: one that
    # changes another kind compiles none of this one's programs anew
    flags = {"mamba": {"state": state}, "gmu": {},
             "diff": {"lam": lam, "subln": subln, "cross_kv": cross_kv,
                      "kv_cotangent": kv_cotangent}}
    hps = [_static({"eps": eps, "d_inner": cfg["mamba_d_inner"],
                    "d_state": cfg["mamba_d_state"],
                    "dt_rank": cfg["mamba_dt_rank"], **flags[entry["kind"]]})
           for entry in plan]
    specs = [_static(entry) for entry in plan]

    p, table, order = _groups(make_weights())
    m = jax.tree.map(lambda a: np.zeros(a.shape, np.float32), p)   # host
    v = jax.tree.map(lambda a: np.zeros(a.shape, np.float32), p)
    losses, grad1 = [], None
    n_layers = len(order)

    def writer_of(i):
        """The layer whose output layer ``i`` reads, or None."""
        slot = plan[i]["reads"]
        if slot is None:
            return None
        if slot == "memory" and memory_at is not None:
            return memory_at
        return next(j for j in range(i) if plan[j]["writes"] == slot)

    read = {writer_of(i) for i in range(n_layers)} - {None}

    for t in range(1, steps + 1):
        tokens = jnp.asarray(batches[t - 1], jnp.int32)
        inputs, targets = tokens[:, :-1], tokens[:, 1:]
        n_rows, length = inputs.shape
        rows = range(n_rows)
        n_tokens = n_rows * length

        def handed_to(i, r):
            w = writer_of(i)
            if w is None:
                return None
            if plan[i]["kind"] == "diff" and cross_kv == "own":
                lw = p[order[w]]
                return (lw["diff.wk"], lw["diff.bk"], lw["diff.wv"],
                        lw["diff.bv"])
            return outs[w][r]

        xs, outs = [[p["embed"]["embed"][inputs[r]] for r in rows]], []
        for i, name in enumerate(order):
            got = jax.block_until_ready(
                [_layer_fwd(p[name], x, handed_to(i, r), specs[i], hps[i],
                            precision) for r, x in zip(rows, xs[-1])])
            xs.append([x for x, _ in got])
            # (what nobody reads is not kept)
            outs.append([out if i in read else None for _, out in got])

        loss, g_head, dxs = 0.0, None, []
        head = {**p["final"], "embed": p["embed"]["embed"]}
        for r in rows:
            l_r, (g_r, dx_r) = _head_vg(head, xs[-1][r], targets[r], eps,
                                        n_tokens, precision)
            loss = loss + l_r
            g_head = g_r if g_head is None else _tree_add(g_head, g_r)
            dxs.append(dx_r)
        xs.pop()
        losses.append(float(loss))
        del head

        norms = {}

        def update(name, g):
            p[name], m_new, v_new, norms[name] = _adamw(
                p[name], m[name], v[name], g, float(t), opt)
            m[name], v[name] = jax.device_get((m_new, v_new))

        g_embed = g_head.pop("embed")
        update("final", g_head)
        # the cotangent of what each layer handed on, summed over its
        # readers (None: nobody read it)
        douts = [[None for _ in rows] for _ in range(n_layers)]
        for i in reversed(range(n_layers)):
            name, g_layer, x_in = order[i], None, xs.pop()
            for r in rows:
                handed = handed_to(i, r)
                g_r, dxs[r], d_handed = _layer_bwd(
                    p[name], x_in[r], handed, dxs[r], douts[i][r], specs[i],
                    hps[i], precision)
                if handed is not None and cross_kv != "own":
                    w = writer_of(i)
                    douts[w][r] = d_handed if douts[w][r] is None else \
                        _tree_add(douts[w][r], d_handed)
                g_layer = g_r if g_layer is None else _tree_add(g_layer, g_r)
            outs[i] = douts[i] = None
            update(name, g_layer)
            jax.block_until_ready(dxs)
        update("embed", {"embed": g_embed + _embed_grad(
            inputs, jnp.stack(dxs), p["embed"]["embed"])})
        if t == 1:
            grad1 = _leaf_table(norms, table)

    del m, v
    start = _groups(make_weights())[0]
    change = {g: {k: _diff_norm(p[g][k], start[g][k]) for k in p[g]}
              for g in p}
    return {"losses": losses, "grad1_norm": grad1,
            "change_norm": _leaf_table(change, table),
            "rule_probe": rule_probe(
                rule_probe_inputs(probe_seed, cfg, n_rows, length), state)}
