"""Pallas flash-attention kernels for a single TPU chip.

The hot op of the model stack: blockwise attention with running-max
softmax so the [L, L] score matrix never leaves VMEM.  (Pallas guide:
grid + BlockSpec pattern; preferred_element_type for MXU dots.)

Two kernels behind a ``custom_vjp``, made the same way.  The forward
(``flash_attention_fwd``) runs a grid over Q blocks and a loop over the
K/V blocks each sees, and also emits the per-row log-sum-exp; the
backward (``flash_attention_bwd``) recomputes the probabilities tile by
tile from (q, k, v, out, lse): a grid over K/V blocks, a loop over the
Q blocks that see each, ``dk``/``dv`` carried by the loop and ``dq``
accumulated in a float32 VMEM scratch, so no ``[L, block]`` tile goes
to HBM in either direction.  In both:

  * the MXU gets its operands in the input dtype with float32
    accumulation (``p`` cast to ``v``'s dtype or ``dout``'s, ``ds`` to
    ``q``'s); the scale is applied to the float32 scores; the running
    max and sum, ``lse``, ``delta``, ``exp`` and the accumulators are
    float32, cast once at the end;
  * a tile is held transposed, ``[block_k, block_q]``, so a row's
    statistics lie along lanes and broadcast along sublanes, and
    ``lse`` passes between the kernels as ``[L // block, block]`` rows;
  * the blocks are the largest of a short ladder that divides the
    length (512 at the benchmark's shapes: the loop's fixed cost is
    paid per tile);
  * under the causal mask tiles above the diagonal are never visited
    and only the tiles the diagonal crosses are masked.

(K/V blocked through the grid for L = 32k is ROADMAP R-M4: the forward
holds K and V whole per head, the backward q, dout and dq; at 256-wide
heads L = 8,192 is the longest row both hold: 16.8 MB of K and V in the
forward, some 50 MB of the backward's 64.  At 64 score and 128 value
columns both hold L = 16,384.)

What is attended to is a static description (``ops/attention_mask.py``:
``CAUSAL``, ``FULL``, ``BlockDiffusion(seq_len, block)``,
``SlidingWindow(window)``): the kernels
take from it the elementwise predicate and, per Q tile (forward) or K
tile (backward), the ranges of opposite tiles to visit, each range
masked or not.  Tiles the mask empties are never visited.

``k`` and ``v`` may have fewer heads than ``q`` (grouped K/V): query
head ``h`` reads K/V head ``h // group`` through the block index, so
K/V are never repeated in HBM; the backward's grid runs the group's
query heads one after another over each K/V head and sums ``dk``/``dv``
in a float32 VMEM scratch.

Latent attention (``models/mla.py``) scores over more columns than it
sums: ``v`` may be narrower or wider than ``k``, and a second, rotary
part of the score may come with its own operands, ``q_rope [.., H, R]``
and ``k_rope [.., R]`` -- ONE rotary key a position, shared by all the
heads of a row:

    s = (q . k + q_rope . k_rope) * (D + R) ** -0.5

Both kernels take the two extra operands as further refs of the same
grid (no third and fourth kernel; without them what is traced is the
kernel as it was): the shared key's block index is the row's, so it is
fetched once a row and not once a head, and the backward returns
``dq_rope`` per head and ``dk_rope`` summed over the row's heads in a
float32 VMEM scratch, as grouped K/V sums ``dk``/``dv``.

The ``custom_vjp``'s residuals are (q, k, v, the rotary pair, out, lse).  ``out`` and
``lse`` carry the names ``RESIDUAL_NAMES``: a layer rematerialised under
``models.transformer.remat_layer`` keeps those two always (as much
again as the layer's input where H * D is ``d_model``, and 4 bytes a
row) and so runs this forward kernel once a layer, not again in the
backward pass; q, k and v -- three times the bytes for less time -- are
the caller's to name, and are kept where the step's plan finds room for
them (``models/remat.py``), else recomputed from the layer's input.
(Named here, heads first as the kernels read them, they came back no
faster on the v5e than named by the caller: ledger and PERF.md section
6, PR 38.)

``attention()`` picks the kernel on TPU and the jnp reference
(ops.ring_attention.full_attention) elsewhere; tests run the kernel in
Pallas interpret mode.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.attention_mask import CAUSAL

_NEG_INF = -1e30
# What the forward kernel writes and the backward kernel reads, named
# where the custom_vjp makes them its residuals (out [BH, L, D] in the
# input dtype, lse [BH, L] float32): a ``jax.checkpoint`` whose policy
# saves these names (``models.transformer.remat_layer``) does not run the
# forward kernel again in the backward pass.  q, k, v carry no name.
RESIDUAL_NAMES = ("flash_out", "flash_lse")
# dot_general dimension numbers: contract the minor dims (a @ b.T) and
# the major dims (a.T @ b).
_NT = (((1,), (1,)), ((), ()))
_TN = (((0,), (0,)), ((), ()))
# The forward's block (both ways), the largest that divides the span a
# tile may not straddle.  On the v5e in bf16, ms a call, causal at
# [64, 4096, 128] / block diffusion at [128 over 16, 8192, 128] (PERF.md,
# PR 29): 512 -> 2.95 / 15.7, 256 -> 5.77 / 25.1, 128 -> 12.7 / 52.9 (the
# loop's fixed cost is paid per tile); 1024 x 512 2.85 / 19.5 and
# 512 x 1024 3.01 / 20.0: a larger tile visits more of what the block
# mask empties.  K and V whole at 8,192 positions fit the 16 MB a
# kernel gets unasked.
_FWD_BLOCKS = (512, 256, 128)
# The backward's block (both ways), the largest that divides L.  On the
# v5e at [64, 4096, 128] bf16 (PERF.md, PR 27): 512 -> 5.4 ms a call,
# 256 -> 7.1, 128 -> 15.8; 1024 or unequal blocks are no faster.
_BWD_BLOCKS = (512, 256, 128)
# q, dout and dq whole per head (double-buffered), the float32 dq
# scratch and a few [block, block] float32 tiles: 13 MB at L = 4096,
# D = 128 bf16, over the 16 MB a kernel gets unasked (the chip has 128).
_BWD_VMEM_BYTES = 64 * 2 ** 20
# The forward holds K, V and, with a rotary part, the shared rotary key
# whole per head, double-buffered (a 64-column key takes a whole
# 128-lane tile), beside the q and out blocks and a few [block, block]
# float32 tiles.  What they come to decides the limit: 8 MB (K and V at
# L = 8192, D = 128 bf16) fits the 16 MB a kernel gets unasked and is
# compiled as it was; 12 MB (latent attention's three at L = 8192) and
# 16.8 MB (K and V at L = 8192, D = 256) get this limit instead.
_FWD_UNASKED_BYTES = 10 * 2 ** 20
_FWD_VMEM_BYTES = 64 * 2 ** 20


def _flash_kernel(q_ref, k_ref, v_ref, *refs, block_k: int, mask,
                  scale: float):
    # Grid (query head, q block).  q_ref/o_ref: [block_q, D]; k_ref/v_ref:
    # [L, D], resident across a K/V head's query heads and q blocks;
    # lse_ref: [L // block_q, block_q], resident across a head's q
    # blocks, one row each.  Tiles are held transposed, [block_k,
    # block_q], as the backward holds them: the row statistics are
    # [1, block_q], dense along lanes, they broadcast along sublanes, and
    # lse leaves as the row the backward reads.  acc is out transposed,
    # [Dv, block_q].  With a rotary part, ``refs`` starts with qr_ref
    # [block_q, R] and kr_ref [L, R], resident across a row's heads.
    *rope, o_ref, lse_ref = refs
    block_q, d = q_ref.shape[0], v_ref.shape[1]
    q_blk = pl.program_id(1)
    f32 = jnp.float32
    q = q_ref[...]
    if rope:
        qr_ref, kr_ref = rope
        qr = qr_ref[...]

    def body(masked, i, carry):
        acc, m_i, l_i = carry
        keys = pl.ds(pl.multiple_of(i * block_k, block_k), block_k)
        k = k_ref[keys, :]
        v = v_ref[keys, :]
        # Scaled after the product: the operands go to the MXU as they
        # arrived, and bfloat16 products are exact in float32.
        s = jax.lax.dot_general(k, q, _NT, preferred_element_type=f32)
        if rope:
            s = s + jax.lax.dot_general(kr_ref[keys, :], qr, _NT,
                                        preferred_element_type=f32)
        s = s * scale
        if masked:
            q_pos = q_blk * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_k, block_q), 1)
            k_pos = i * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_k, block_q), 0)
            s = jnp.where(mask.allowed(q_pos, k_pos), s, _NEG_INF)
        m_new = jnp.maximum(m_i, jnp.max(s, axis=0, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_i - m_new)
        l_new = l_i * corr + jnp.sum(p, axis=0, keepdims=True)
        acc = acc * corr + jax.lax.dot_general(
            v, p.astype(v.dtype), _TN, preferred_element_type=f32)
        return acc, m_new, l_new

    carry = (jnp.zeros((d, block_q), f32),
             jnp.full((1, block_q), _NEG_INF, f32),
             jnp.zeros((1, block_q), f32))
    # Only the K tiles the mask admits for this Q tile.
    for first, stop, masked in mask.k_ranges(q_blk, block_q, block_k,
                                             k_ref.shape[0] // block_k):
        carry = jax.lax.fori_loop(first, stop,
                                  functools.partial(body, masked), carry)
    acc, m, l = carry
    l = jnp.maximum(l, 1e-20)
    o_ref[...] = (acc / l).T.astype(o_ref.dtype)
    lse_ref[pl.ds(q_blk, 1), :] = m + jnp.log(l)


def _flash_forward(qh, kh, vh, rope, mask, block_q, block_k, interpret):
    """q [BH, L, D], k [BH // group, L, D], v [BH // group, L, Dv],
    ``rope`` None or (q_rope [BH, L, R], k_rope [B, L, R]) -> (out
    [BH, L, Dv], lse [BH, L] f32).  A block that is None is chosen from
    the span."""
    BH, L, D = qh.shape
    Dv = vh.shape[-1]
    group = BH // kh.shape[0]
    span = mask.tile_span(L)
    chosen = next((b for b in _FWD_BLOCKS if span % b == 0), _FWD_BLOCKS[-1])
    block_q, block_k = block_q or chosen, block_k or chosen
    if span % block_q or span % block_k:
        raise ValueError(
            f"sequence length {span} must be a multiple of the block sizes "
            f"({block_q}, {block_k}); pad upstream")
    nq = L // block_q
    in_specs = [
        pl.BlockSpec((None, block_q, D), lambda b, i: (b, i, 0)),
        # a group's query heads follow one another, so a K/V head
        # is fetched once for all of them
        pl.BlockSpec((None, L, D), lambda b, i: (b // group, 0, 0)),
        pl.BlockSpec((None, L, Dv), lambda b, i: (b // group, 0, 0)),
    ]
    operands, rotary = (qh, kh, vh), 0
    if rope is not None:
        rotary = rope[0].shape[-1]
        heads = BH // rope[1].shape[0]           # query heads a row
        in_specs += [
            pl.BlockSpec((None, block_q, rotary), lambda b, i: (b, i, 0)),
            # a row's heads follow one another: its one rotary key is
            # fetched once for all of them
            pl.BlockSpec((None, L, rotary), lambda b, i: (b // heads, 0, 0)),
        ]
        operands = operands + tuple(rope)
    # the whole operands, double-buffered, a column group a lane tile
    held = 2 * L * qh.dtype.itemsize * sum(
        -(-width // 128) * 128 for width in (D, Dv, rotary) if width)
    limit = _FWD_VMEM_BYTES if held > _FWD_UNASKED_BYTES else None
    kernel = functools.partial(_flash_kernel, block_k=block_k, mask=mask,
                               scale=(D + rotary) ** -0.5)
    out, lse = pl.pallas_call(
        kernel,
        grid=(BH, nq),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((None, block_q, Dv), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, nq, block_q), lambda b, i: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, L, Dv), qh.dtype),
            jax.ShapeDtypeStruct((BH, nq, block_q), jnp.float32),
        ],
        # a head's lse block is written a row a q block: that axis runs
        # in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=limit),
        interpret=interpret,
        name="flash_attention_fwd",
    )(*operands)
    return out, lse.reshape(BH, L)


def _flash_bwd_kernel(q_ref, k_ref, v_ref, *refs, block_q: int, mask,
                      scale: float, grouped: bool, heads: int):
    # Grid (K/V head, query head of its group, k block).
    # k_ref/dk_ref: [block_k, D], v_ref/dv_ref: [block_k, Dv];
    # q_ref/dq_ref: [L, D], do_ref: [L, Dv], resident across a query
    # head's k blocks; lse_ref/delta_ref: [L // block_q, block_q], one
    # row a q block; dq_acc: [L, D] f32; kv_acc (grouped K/V only): dk
    # and dv of the K/V head so far, [L, D] / [L, Dv] f32.  Tiles are
    # held transposed, [block_k, block_q], so the row statistics
    # broadcast along sublanes and dk/dv need no transpose.  With a
    # rotary part (``heads`` query heads a row, else 0) ``refs`` starts
    # with qr_ref [L, R] and kr_ref [block_k, R] and the outputs end
    # with dqr_ref [L, R] and dkr_ref [L, R], the row's, resident across
    # its heads; the scratch ends with dqr_acc and dkr_acc, [L, R] f32.
    if heads:
        qr_ref, kr_ref, *refs = refs
    (do_ref, lse_ref, delta_ref, dq_ref, dk_ref, dv_ref, *refs) = refs
    if heads:
        dqr_ref, dkr_ref, *refs, dqr_acc, dkr_acc = refs
    dq_acc, *kv_acc = refs
    block_k = k_ref.shape[0]
    num_q = q_ref.shape[0] // block_q
    head, k_blk = pl.program_id(1), pl.program_id(2)
    f32 = jnp.float32

    @pl.when(k_blk == 0)
    def _():
        dq_acc[...] = jnp.zeros_like(dq_acc)
        if heads:
            dqr_acc[...] = jnp.zeros_like(dqr_acc)

    k = k_ref[...]
    v = v_ref[...]
    if heads:
        kr = kr_ref[...]

    def body(masked, i, carry):
        dk, dv, *dkr = carry
        rows = pl.ds(pl.multiple_of(i * block_q, block_q), block_q)
        q = q_ref[rows, :]
        do = do_ref[rows, :]
        s = jax.lax.dot_general(k, q, _NT, preferred_element_type=f32)
        if heads:
            qr = qr_ref[rows, :]
            s = s + jax.lax.dot_general(kr, qr, _NT,
                                        preferred_element_type=f32)
        s = s * scale
        if masked:
            q_pos = i * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_k, block_q), 1)
            k_pos = k_blk * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_k, block_q), 0)
            s = jnp.where(mask.allowed(q_pos, k_pos), s, _NEG_INF)
        p = jnp.exp(s - lse_ref[pl.ds(i, 1), :])
        dv = dv + jnp.dot(p.astype(do.dtype), do, preferred_element_type=f32)
        dp = jax.lax.dot_general(v, do, _NT, preferred_element_type=f32)
        ds = (p * (dp - delta_ref[pl.ds(i, 1), :]) * scale).astype(q.dtype)
        dk = dk + jnp.dot(ds, q, preferred_element_type=f32)
        dq_acc[rows, :] += jax.lax.dot_general(ds, k, _TN,
                                               preferred_element_type=f32)
        if not heads:
            return dk, dv
        dqr_acc[rows, :] += jax.lax.dot_general(ds, kr, _TN,
                                                preferred_element_type=f32)
        return dk, dv, dkr[0] + jnp.dot(ds, qr, preferred_element_type=f32)

    zeros = jnp.zeros(k_ref.shape, f32)
    carry = (zeros, zeros if v_ref.shape == k_ref.shape
             else jnp.zeros(v_ref.shape, f32))
    if heads:
        carry += (jnp.zeros(kr_ref.shape, f32),)
    # Only the Q tiles that see this K tile.
    for first, stop, masked in mask.q_ranges(k_blk, block_q, block_k, num_q):
        carry = jax.lax.fori_loop(first, stop,
                                  functools.partial(body, masked), carry)
    dk, dv, *dkr = carry
    if grouped:
        # The K/V head's block comes round once a query head; every
        # visit writes the sum so far, the last one the whole of it.
        dk_acc, dv_acc = kv_acc
        keys = pl.ds(pl.multiple_of(k_blk * block_k, block_k), block_k)

        @pl.when(head == 0)
        def _():
            dk_acc[keys, :] = dk
            dv_acc[keys, :] = dv

        @pl.when(head > 0)
        def _():
            dk_acc[keys, :] += dk
            dv_acc[keys, :] += dv

        dk, dv = dk_acc[keys, :], dv_acc[keys, :]
    dk_ref[...] = dk.astype(dk_ref.dtype)
    dv_ref[...] = dv.astype(dv_ref.dtype)
    if heads:
        # The row's one rotary key comes round once a query head of the
        # row, likewise.
        keys = pl.ds(pl.multiple_of(k_blk * block_k, block_k), block_k)
        in_row = (pl.program_id(0) * pl.num_programs(1) + head) % heads

        @pl.when(in_row == 0)
        def _():
            dkr_acc[keys, :] = dkr[0]

        @pl.when(in_row > 0)
        def _():
            dkr_acc[keys, :] += dkr[0]

        dkr_ref[keys, :] = dkr_acc[keys, :].astype(dkr_ref.dtype)

    @pl.when(k_blk == pl.num_programs(2) - 1)
    def _():
        dq_ref[...] = dq_acc[...].astype(dq_ref.dtype)
        if heads:
            dqr_ref[...] = dqr_acc[...].astype(dqr_ref.dtype)


@jax.named_scope("flash_attention_bwd")
def _flash_backward(qh, kh, vh, rope, out, lse, dout, mask, interpret):
    """(dq [BH, L, D], dk [BH // group, L, D], dv [BH // group, L, Dv],
    and None or (dq_rope [BH, L, R], dk_rope [B, L, R])) from the
    forward's residuals."""
    BH, L, D = qh.shape
    Dv = vh.shape[-1]
    heads_kv = kh.shape[0]
    group = BH // heads_kv
    span = mask.tile_span(L)
    block = next((b for b in _BWD_BLOCKS if span % b == 0), None)
    if block is None:
        raise ValueError(
            f"sequence length {span} must be a multiple of "
            f"{_BWD_BLOCKS[-1]} for the backward kernel; pad upstream")
    nq = L // block
    f32 = jnp.float32
    delta = jnp.sum(dout.astype(f32) * out.astype(f32), axis=-1)  # [BH, L]

    def whole(width):
        return pl.BlockSpec((None, L, width),
                            lambda b, g, j: (b * group + g, 0, 0))

    def blocked(width):
        return pl.BlockSpec((None, block, width), lambda b, g, j: (b, j, 0))

    stats = pl.BlockSpec((None, nq, block),
                         lambda b, g, j: (b * group + g, 0, 0))
    in_specs = [whole(D), blocked(D), blocked(Dv)]
    operands = (qh, kh, vh)
    out_specs = [whole(D), blocked(D), blocked(Dv)]
    results = [qh, kh, vh]
    scratch = [pltpu.VMEM((L, D), f32)]
    if group > 1:
        scratch += [pltpu.VMEM((L, D), f32), pltpu.VMEM((L, Dv), f32)]
    rotary = heads = 0
    # dq accumulates over a query head's k blocks and dk/dv over a
    # K/V head's query heads: those axes run in order.
    order = ("parallel", "arbitrary", "arbitrary")
    if rope is not None:
        rotary = rope[0].shape[-1]
        heads = BH // rope[1].shape[0]           # query heads a row
        # a row's heads follow one another: its rotary key's gradient
        # stays in VMEM while they add to it
        row = pl.BlockSpec(
            (None, L, rotary), lambda b, g, j: ((b * group + g) // heads,
                                                0, 0))
        in_specs += [whole(rotary), pl.BlockSpec(
            (None, block, rotary),
            lambda b, g, j: ((b * group + g) // heads, j, 0))]
        operands += tuple(rope)
        out_specs += [whole(rotary), row]
        results += list(rope)
        scratch += [pltpu.VMEM((L, rotary), f32)] * 2
        # ... so the heads run in order too
        order = ("arbitrary",) * 3
    kernel = functools.partial(_flash_bwd_kernel, block_q=block, mask=mask,
                               scale=(D + rotary) ** -0.5,
                               grouped=group > 1, heads=heads)
    grads = pl.pallas_call(
        kernel,
        grid=(heads_kv, group, nq),
        in_specs=in_specs + [whole(Dv), stats, stats],
        out_specs=out_specs,
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype) for x in results],
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=order,
            vmem_limit_bytes=_BWD_VMEM_BYTES),
        interpret=interpret,
        name="flash_attention_bwd",
    )(*operands, dout, lse.reshape(BH, nq, block),
      delta.reshape(BH, nq, block))
    return (*grads[:3], None if rope is None else tuple(grads[3:]))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _flash(qh, kh, vh, rope, mask, block_q, block_k, interpret):
    return _kept_out(_flash_forward(qh, kh, vh, rope, mask, block_q, block_k,
                                    interpret)[0])


def _kept_out(out):
    """``out`` under its name in the primal function too.  A checkpoint
    policy reads the ``fwd`` rule's names; whoever reads the forward's
    jaxpr alone, as ``models/remat.py`` does to plan, sees here that
    ``out`` is kept and the kernel not run again."""
    return checkpoint_name(out, RESIDUAL_NAMES[0])


def _flash_vjp_fwd(qh, kh, vh, rope, mask, block_q, block_k, interpret):
    out, lse = _flash_forward(qh, kh, vh, rope, mask, block_q, block_k,
                              interpret)
    out = checkpoint_name(out, RESIDUAL_NAMES[0])
    lse = checkpoint_name(lse, RESIDUAL_NAMES[1])
    return out, (qh, kh, vh, rope, out, lse)


def _flash_vjp_bwd(mask, block_q, block_k, interpret, res, dout):
    del block_q, block_k     # the forward's; the backward picks its own
    return _flash_backward(*res, dout, mask, interpret)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


@functools.partial(jax.jit, static_argnames=("mask", "block_q", "block_k",
                                             "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    mask=CAUSAL, block_q: int | None = None,
                    block_k: int | None = None, interpret: bool = False,
                    q_rope: jax.Array | None = None,
                    k_rope: jax.Array | None = None) -> jax.Array:
    """q: [B, L, H, D]; k: [B, L, H // group, D]; v: [B, L, H // group,
    Dv] -> [B, L, H, Dv].  ``q_rope`` [B, L, H, R] with ``k_rope``
    [B, L, R] (one rotary key a position, shared by the heads) add
    ``q_rope . k_rope`` to the score, scaled by ``(D + R) ** -0.5``.
    ``mask`` is one of ``ops.attention_mask``'s descriptions.  The
    forward's blocks are chosen from L where they are not given; L must
    be a multiple of them (pad upstream).  ``interpret`` runs the kernel
    in the Pallas interpreter (CPU tests)."""
    B, L, H, D = q.shape
    heads_kv = k.shape[2]
    if H % heads_kv or v.shape[:3] != k.shape[:3] or k.shape[3] != D:
        raise ValueError(f"{H} query heads of {D} over K/V of shapes "
                         f"{k.shape}, {v.shape}")
    if (q_rope is None) != (k_rope is None):
        raise ValueError("q_rope and k_rope come together")
    # Collapse batch x heads into the leading grid dimension: query
    # head b * H + h reads K/V head (b * H + h) // group.
    qh = q.transpose(0, 2, 1, 3).reshape(B * H, L, D)
    kh = k.transpose(0, 2, 1, 3).reshape(B * heads_kv, L, D)
    vh = v.transpose(0, 2, 1, 3).reshape(B * heads_kv, L, v.shape[3])
    rope = None
    if q_rope is not None:
        if q_rope.shape[:3] != (B, L, H) or \
                k_rope.shape != (B, L, q_rope.shape[3]):
            raise ValueError(f"rotary parts of shapes {q_rope.shape}, "
                             f"{k_rope.shape} beside q {q.shape}")
        rope = (q_rope.transpose(0, 2, 1, 3).reshape(B * H, L, -1), k_rope)
    out = _flash(qh, kh, vh, rope, mask, block_q, block_k, interpret)
    return out.reshape(B, H, L, -1).transpose(0, 2, 1, 3)


def attention(q: jax.Array, k: jax.Array, v: jax.Array, mask=CAUSAL,
              q_rope: jax.Array | None = None,
              k_rope: jax.Array | None = None) -> jax.Array:
    """Backend dispatch: pallas kernel on TPU, jnp reference elsewhere.
    The forward kernel asks for a VMEM limit by the bytes it holds whole
    (K, V and the rotary key where there is one: ``_FWD_UNASKED_BYTES``),
    not by which operands came."""
    from ray_tpu.ops.ring_attention import full_attention
    # Trace-time decision: the backend is fixed per process.
    if (jax.default_backend() == "tpu" and q.shape[1] % 128 == 0
            and q.shape[-1] >= 64):
        return flash_attention(q, k, v, mask=mask, q_rope=q_rope,
                               k_rope=k_rope)
    return full_attention(q, k, v, mask=mask, q_rope=q_rope, k_rope=k_rope)
