"""Pod-sharded scheduling solve: the node axis across local devices.

The single-device kernels in ``jax_backend`` solve the (classes x
nodes) waterfill / tick / bundle-pack on ONE chip.  This module shards
the NODE axis across every visible device with ``shard_map`` over a 1-D
``jax.sharding.Mesh`` (axis name ``"nodes"``), so each device owns a
contiguous block of ``n_local = n_pad / n_shards`` node columns and the
whole padded ring is the shard-major concatenation of the blocks.

Reduction semantics per bucket step (see ``_sharded_fill_step``):

  * every shard computes the SAME per-node cap/score/bucket math as
    ``_bucket_fill_step`` on its local columns (elementwise — bitwise
    identical to the single-device kernel);
  * the within-bucket exclusive prefix splits into a shard-local
    two-level blocked prefix plus a cross-shard exclusive offset:
    ``all_gather`` of the [B] per-shard bucket totals gives every shard
    the full [n_shards, B] table, from which it takes its own exclusive
    prefix (offset) and the global bucket totals S;
  * the rotation decomposition (P/Q/S from ``_bucket_fill_step``) needs
    Q[b] = global prefix at the rotation start: exactly one shard owns
    that column, contributes its value, and a ``psum`` replicates it;
  * the wrap term compares GLOBAL lane index (shard_lo + local lane)
    against the shift, so rotated fill order is identical to the
    single-device ring.

All sums are integer-valued f32, so as long as per-bucket totals stay
below 2**24 every reduction is exact in ANY association order:
sharded output is BIT-identical to the single-device kernel whenever
both use the same padded ring width (``n_pad``).  Because this module
pads N to a multiple of ``_GROUP * n_shards`` while the single-device
path pads to ``_GROUP``, a non-aligned N widens the ring and the
per-class rotation ``(c * _ROT_STRIDE) % n_pad`` lands elsewhere —
allocations then differ only in within-bucket tie-break order
(feasibility-parity; the parity tests pin BIT-parity against the numpy
oracle evaluated on the sharded ring width, and against the
single-device kernel on aligned shapes).

Bundle packing's cross-shard argmax keeps the exact ``jnp.argmax``
first-max tie-break: each shard reports (local first-max value, local
index); the winner is the FIRST shard attaining the global max, which
in shard-major concatenation order is precisely the global first-max.

There is no run-time switch back to the single-device path: a sharded
compile or device error propagates from the ``BatchSolver`` entry
points and is counted under ``DeviceRuntimeSolver.stats
["device_errors"]`` on the live tick.
"""

from __future__ import annotations

import functools
from typing import Optional

import numpy as np

from ray_tpu._private.config import get_config
from ray_tpu.scheduler.jax_backend import (
    _BIG, _COST_BUCKETS, _GROUP, _NUM_BUCKETS, _ROT_STRIDE, _UTIL_LEVELS,
    _pad_to, _round_up)

_AXIS = "nodes"


def plan_shards(n_nodes: int) -> int:
    """Shard count for a solve over ``n_nodes`` nodes (1 = don't shard).

    Gate: ``solver_shard_backend`` ("off" never, "force" whenever >1
    device, "auto" only at ``solver_shard_min_nodes`` scale — below
    that the collective latency outweighs the per-shard shrink) and
    the visible device count.
    """
    cfg = get_config()
    mode = cfg.solver_shard_backend
    if mode == "off":
        return 1
    if mode != "force" and n_nodes < cfg.solver_shard_min_nodes:
        return 1
    import jax
    return len(jax.devices())


def pads_sharded(C: int, N: int, R: int, n_shards: int):
    """Like ``BatchSolver._pads`` but the node ring is padded so every
    shard owns a whole number of 128-lane groups."""
    return (_round_up(max(C, 1), 8),
            _round_up(max(N, 8), _GROUP * n_shards),
            _round_up(max(R, 1), 8))


@functools.lru_cache(maxsize=4)
def _mesh(n_shards: int):
    import jax
    from jax.sharding import Mesh
    devs = jax.devices()
    if len(devs) < n_shards:
        raise ValueError(
            f"mesh wants {n_shards} devices, only {len(devs)} visible")
    return Mesh(np.array(devs[:n_shards]), axis_names=(_AXIS,))


def node_sharding(n_shards: int, spec_axes=(None, _AXIS)):
    """NamedSharding placing the node axis across the mesh (default:
    [R, N] layout — nodes on axis 1)."""
    from jax.sharding import NamedSharding, PartitionSpec
    return NamedSharding(_mesh(n_shards), PartitionSpec(*spec_axes))


def replicated_sharding(n_shards: int):
    from jax.sharding import NamedSharding, PartitionSpec
    return NamedSharding(_mesh(n_shards), PartitionSpec())


# ---------------------------------------------------------------------------
# Per-class fill with cross-shard prefix reduction.
# ---------------------------------------------------------------------------

def _sharded_fill_step(av, total, d, cnt, is_accel, shift, cost_row,
                       invert, accel_node, empty, spread_threshold,
                       *, n_shards: int):
    """One class's waterfill step on ONE shard's [R, n_local] block.

    Local math is the verbatim ``_bucket_fill_step`` formulation; only
    the prefix acquires the cross-shard offset / Q / wrap corrections
    described in the module docstring.  Returns (new_av, take[n_local]).
    """
    import jax
    import jax.numpy as jnp

    eps = 1e-6
    n_loc = av.shape[1]
    demanded = d > 0                                       # [R]
    any_demand = jnp.any(demanded)
    ratios = jnp.where(demanded[:, None],
                       av / jnp.maximum(d[:, None], eps), _BIG)
    cap = jnp.floor(jnp.min(ratios, axis=0) + eps)         # [n_loc]
    cap = jnp.clip(cap, 0.0, cnt)
    util = jnp.where(total > 0, (total - av) / jnp.maximum(total, eps), 0.0)
    score_demanded = jnp.max(
        jnp.where(demanded[:, None], util, -_BIG), axis=0)
    score_overall = jnp.max(util, axis=0)
    score = jnp.where(any_demand, score_demanded, score_overall)
    score = jnp.where(invert > 0, 1.0 - score, score)
    scale = _UTIL_LEVELS / jnp.maximum(1.0 - spread_threshold, eps)
    lvl = jnp.clip(
        jnp.floor((score - spread_threshold) * scale) + 1.0,
        1.0, float(_UTIL_LEVELS))
    b_util = jnp.where(score < spread_threshold, 0.0, lvl)
    cost_b = jnp.floor(cost_row * scale + 0.5)
    bucket = jnp.clip(b_util + float(_COST_BUCKETS) + cost_b,
                      0.0, float(_COST_BUCKETS + _UTIL_LEVELS))
    bucket = jnp.where(jnp.logical_and(accel_node, ~is_accel),
                       float(_COST_BUCKETS + _UTIL_LEVELS + 1), bucket)
    bucket = jnp.where(empty, float(_NUM_BUCKETS - 1), bucket)
    bucket = bucket.astype(jnp.int32)
    onehot = (bucket[None, :] ==
              jnp.arange(_NUM_BUCKETS, dtype=jnp.int32)[:, None])
    cap_oh = jnp.where(onehot, cap[None, :], 0.0)          # [B, n_loc]
    # Shard-local two-level blocked prefix (identical structure to the
    # single-device kernel over this shard's groups).
    g = cap_oh.reshape(_NUM_BUCKETS, n_loc // _GROUP, _GROUP)
    gsum = jnp.sum(g, axis=2)                              # [B, G_loc]
    gprefix = jnp.cumsum(gsum, axis=1) - gsum
    tri = jnp.triu(jnp.ones((_GROUP, _GROUP), jnp.float32), k=1)
    within = jax.lax.dot_general(
        g, tri, (((2,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST)
    p_loc = (within + gprefix[:, :, None]).reshape(_NUM_BUCKETS, n_loc)
    s_loc = jnp.sum(gsum, axis=1)                          # [B] shard total
    # Cross-shard reduction: every shard sees the full per-shard bucket
    # totals, takes its own exclusive offset and the global totals S.
    gathered = jax.lax.all_gather(s_loc, _AXIS)            # [n_shards, B]
    me = jax.lax.axis_index(_AXIS)
    shard_off = jnp.sum(
        jnp.where(jnp.arange(n_shards)[:, None] < me, gathered, 0.0),
        axis=0)                                            # [B] exclusive
    btotal = jnp.sum(gathered, axis=0)                     # [B] (= S)
    p_nat = p_loc + shard_off[:, None]                     # global prefix
    # Q[b] = global prefix at the rotation start column: owned by
    # exactly one shard, replicated by psum.
    lo = me * n_loc
    shift_loc = shift - lo
    own = (shift_loc >= 0) & (shift_loc < n_loc)
    q_piece = jax.lax.dynamic_slice_in_dim(
        p_nat, jnp.clip(shift_loc, 0, n_loc - 1), 1, axis=1)[:, 0]
    q_at_shift = jax.lax.psum(
        jnp.where(own, q_piece, 0.0), _AXIS)               # [B] (= Q)
    bprefix = jnp.cumsum(btotal) - btotal
    wrap = jnp.where(lo + jnp.arange(n_loc) < shift,
                     btotal[:, None], 0.0)                 # [B, n_loc]
    prefix_bn = p_nat - q_at_shift[:, None] + wrap + bprefix[:, None]
    prefix = jnp.sum(jnp.where(onehot, prefix_bn, 0.0), axis=0)
    take = jnp.clip(cnt - prefix, 0.0, cap)
    av = av - take[None, :] * d[:, None]
    return av, take


def _sharded_class_fill(av_t, total_t, demand, counts, accel_class,
                        accel_node, spread_threshold, cost, invert,
                        shifts, *, n_shards: int):
    """Scan the sharded fill over all classes (runs INSIDE shard_map:
    av_t/total_t/accel_node/cost are this shard's local blocks)."""
    import jax
    import jax.numpy as jnp

    empty = jnp.max(total_t, axis=0) <= 0

    def body(av, xs):
        d, cnt, is_accel, shift, cost_row = xs
        return _sharded_fill_step(
            av, total_t, d, cnt, is_accel, shift, cost_row, invert,
            accel_node, empty, spread_threshold, n_shards=n_shards)

    av_after, allocs = jax.lax.scan(
        body, av_t, (demand, counts, accel_class, shifts, cost), unroll=8)
    return av_after, allocs


def _sharded_pack_tick(allocs, counts_k, av_pre, demand, nnz_max,
                       n_pad, *, n_shards: int):
    """Per-shard validation + sparse encoding with GLOBAL flat indices.

    Validation bits are reduced across shards (psum) so every shard's
    packed row carries the same (placed, ok); the nnz slot stays
    per-shard and the host sums it while merging rows.
    """
    import jax
    import jax.numpy as jnp

    c_pad, n_loc = allocs.shape
    usage = jnp.einsum("cn,cr->rn", allocs, demand)
    bad_cap = jnp.any(usage > av_pre + 1e-2)
    ok_cap = jax.lax.psum(bad_cap.astype(jnp.float32), _AXIS) == 0
    placed_c = jax.lax.psum(jnp.sum(allocs, axis=1), _AXIS)    # [C] global
    ok_cnt = jnp.all(placed_c <= counts_k + 0.5)
    placed = jnp.sum(placed_c)
    me = jax.lax.axis_index(_AXIS)
    lo = me * n_loc
    flat = allocs.reshape(c_pad * n_loc)
    nz = flat > 0
    nnz_loc = jnp.sum(nz.astype(jnp.int32))
    (pos,) = jnp.nonzero(nz, size=nnz_max, fill_value=c_pad * n_loc)
    live = jnp.arange(nnz_max) < nnz_loc
    posc = jnp.minimum(pos, c_pad * n_loc - 1)
    gidx = (posc // n_loc) * n_pad + lo + (posc % n_loc)
    idx = jnp.where(live, gidx, c_pad * n_pad)
    vals = jnp.where(live, flat[posc], 0.0)
    overflow = jax.lax.psum(
        (nnz_loc > nnz_max).astype(jnp.float32), _AXIS) > 0
    ok = ok_cap & ok_cnt & ~overflow
    return jnp.concatenate([
        idx.astype(jnp.float32), vals,
        jnp.stack([placed, ok.astype(jnp.float32),
                   nnz_loc.astype(jnp.float32)])])


# ---------------------------------------------------------------------------
# Jitted sharded programs (cached per padded shape x shard count).
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=16)
def _jit_sharded_waterfill(c_pad: int, n_pad: int, r_pad: int,
                           n_shards: int):
    """Sharded twin of ``_jit_waterfill`` ([N, R] in, allocs [C, N] out)."""
    import jax
    from jax.sharding import PartitionSpec as P

    mesh = _mesh(n_shards)

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(P(_AXIS, None), P(_AXIS, None), P(), P(), P(_AXIS),
                  P(), P(), P(None, _AXIS), P(), P()),
        out_specs=(P(None, _AXIS), P(_AXIS, None)),
        check_vma=False)
    def solve(avail, total, demand, counts, accel_node, accel_class,
              spread_threshold, cost, invert, shifts):
        av_after, allocs = _sharded_class_fill(
            avail.T, total.T, demand, counts, accel_class, accel_node,
            spread_threshold, cost, invert, shifts, n_shards=n_shards)
        return allocs, av_after.T

    return jax.jit(solve)


@functools.lru_cache(maxsize=16)
def _jit_sharded_solve_tick(c_pad: int, n_pad: int, r_pad: int,
                            nnz_max: int, n_shards: int):
    """Sharded twin of ``_jit_solve_tick``: device-resident sharded
    [R, N] world state in, per-shard packed rows [n_shards, 2*nnz+3]
    out (merge with ``merge_packed``)."""
    import jax
    from jax.sharding import PartitionSpec as P

    assert c_pad * n_pad < (1 << 24), "sparse idx must stay exact in f32"
    mesh = _mesh(n_shards)

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(P(None, _AXIS), P(None, _AXIS), P(), P(), P(_AXIS),
                  P(), P(), P(None, _AXIS)),
        out_specs=P(_AXIS, None),
        check_vma=False)
    def solve(avail_t, total_t, demand, counts, accel_node, accel_class,
              spread_threshold, cost):
        shifts = (np.arange(c_pad, dtype=np.int32) * _ROT_STRIDE) % n_pad
        import jax.numpy as jnp
        _, allocs = _sharded_class_fill(
            avail_t, total_t, demand, counts, accel_class, accel_node,
            spread_threshold, cost, jnp.float32(0.0),
            jnp.asarray(shifts), n_shards=n_shards)
        packed = _sharded_pack_tick(allocs, counts, avail_t, demand,
                                    nnz_max, n_pad, n_shards=n_shards)
        return packed[None, :]

    return jax.jit(solve)


@functools.lru_cache(maxsize=16)
def _jit_sharded_pack_bundles(b_pad: int, n_pad: int, r_pad: int,
                              n_shards: int):
    """Sharded twin of ``_jit_pack_bundles``: per-bundle cross-shard
    argmax with the exact first-max tie-break (see module docstring).
    Outputs are replicated; the host reads shard row 0."""
    import jax
    from jax.sharding import PartitionSpec as P

    mesh = _mesh(n_shards)

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(P(_AXIS, None), P(_AXIS, None), P(), P(_AXIS), P(_AXIS),
                  P(), P()),
        out_specs=(P(_AXIS, None), P(_AXIS, None)),
        check_vma=False)
    def solve(avail, total, demand, excluded, used0, pack_w,
              strict_spread):
        import jax.numpy as jnp
        eps = 1e-6
        n_loc = avail.shape[0]
        me = jax.lax.axis_index(_AXIS)
        alive = jnp.max(total, axis=1) > 0
        node_ok = alive & ~excluded

        def body(carry, d):
            av, used = carry
            demanded = d > 0
            is_real = jnp.any(demanded)
            feasible = jnp.all(av + eps >= d[None, :], axis=1) & node_ok
            feasible = jnp.where(strict_spread > 0,
                                 feasible & ~used, feasible)
            terms = jnp.where(
                demanded[None, :],
                1.0 - (av - d[None, :]) / jnp.maximum(av, 1.0), 0.0)
            nd = jnp.maximum(jnp.sum(demanded.astype(jnp.float32)), 1.0)
            sc = jnp.sum(terms, axis=1) / nd
            sc = sc + pack_w * used.astype(jnp.float32)
            sc = jnp.where(feasible, sc, -_BIG)
            loc_best = jnp.argmax(sc).astype(jnp.int32)
            loc_val = sc[loc_best]
            vals_all = jax.lax.all_gather(loc_val, _AXIS)   # [n_shards]
            idxs_all = jax.lax.all_gather(loc_best, _AXIS)
            win = jnp.argmax(vals_all).astype(jnp.int32)    # first shard
            best = win * n_loc + idxs_all[win]
            ok = is_real & (vals_all[win] > -_BIG / 2)
            hot = ((jnp.arange(n_loc) == idxs_all[win])
                   & (me == win) & ok)                      # [n_loc]
            av = av - jnp.where(hot[:, None], d[None, :], 0.0)
            used = used | hot
            return (av, used), (best, ok)

        (_, _), (idx, ok) = jax.lax.scan(body, (avail, used0), demand)
        return idx[None, :], ok[None, :]

    return jax.jit(solve)


@functools.lru_cache(maxsize=16)
def _jit_sharded_apply_rows(n_pad: int, r_pad: int, k_pad: int,
                            n_shards: int):
    """Dirty-row scatter against the SHARDED device-resident avail
    matrix (GSPMD partitions the scatter; indices stay replicated)."""
    import jax

    sh = node_sharding(n_shards)
    rep = replicated_sharding(n_shards)

    def apply(avail_t, idx, rows):
        return avail_t.at[:, idx].set(rows.T)

    return jax.jit(apply, donate_argnums=(0,),
                   in_shardings=(sh, rep, rep), out_shardings=sh)


# ---------------------------------------------------------------------------
# Host wrappers (the BatchSolver / DeviceRuntimeSolver entry points).
# ---------------------------------------------------------------------------

def solve_matrices_sharded(avail: np.ndarray, total: np.ndarray,
                           demand: np.ndarray, counts: np.ndarray,
                           accel_node: np.ndarray,
                           accel_class: np.ndarray,
                           spread_threshold: float,
                           cost: Optional[np.ndarray],
                           invert_util: bool, zero_shifts: bool,
                           n_shards: int) -> np.ndarray:
    """Sharded one-tick waterfill; same contract as
    ``BatchSolver.solve_matrices`` (alloc [C, N] int64)."""
    import jax
    C, R = demand.shape
    N = avail.shape[0]
    c_pad, n_pad, r_pad = pads_sharded(C, N, R, n_shards)
    cost_p = np.zeros((c_pad, n_pad), np.float32) if cost is None \
        else _pad_to(cost.astype(np.float32), (c_pad, n_pad))
    shifts = np.zeros(c_pad, np.int32) if zero_shifts else \
        np.asarray((np.arange(c_pad) * _ROT_STRIDE) % n_pad, np.int32)
    fn = _jit_sharded_waterfill(c_pad, n_pad, r_pad, n_shards)
    allocs, _ = jax.block_until_ready(fn(
        _pad_to(avail.astype(np.float32), (n_pad, r_pad)),
        _pad_to(total.astype(np.float32), (n_pad, r_pad)),
        _pad_to(demand.astype(np.float32), (c_pad, r_pad)),
        _pad_to(counts.astype(np.float32), (c_pad,)),
        _pad_to(accel_node.astype(bool), (n_pad,)),
        _pad_to(accel_class.astype(bool), (c_pad,)),
        np.float32(spread_threshold), cost_p,
        np.float32(1.0 if invert_util else 0.0), shifts))
    allocs = np.asarray(jax.device_get(allocs))[:C, :N]
    return np.rint(allocs).astype(np.int64)


def solve_bundles_sharded(avail: np.ndarray, total: np.ndarray,
                          demand: np.ndarray, strategy: str,
                          excluded: Optional[np.ndarray],
                          n_shards: int):
    """Sharded bundle->node solve; same contract (and, for any N, the
    same bits) as ``BatchSolver.solve_bundles``."""
    import jax
    B, R = demand.shape
    N = avail.shape[0]
    b_pad = _round_up(max(B, 1), 8)
    n_pad = _round_up(max(N, 8), _GROUP * n_shards)
    r_pad = _round_up(max(R, 1), 8)
    if excluded is None:
        excluded = np.zeros(N, dtype=bool)
    pack_w = {"PACK": 10.0, "SPREAD": -10.0}.get(strategy, 0.0)
    fn = _jit_sharded_pack_bundles(b_pad, n_pad, r_pad, n_shards)
    idx, ok = jax.block_until_ready(fn(
        _pad_to(avail.astype(np.float32), (n_pad, r_pad)),
        _pad_to(total.astype(np.float32), (n_pad, r_pad)),
        _pad_to(demand.astype(np.float32), (b_pad, r_pad)),
        _pad_to(excluded.astype(bool), (n_pad,)),
        np.zeros(n_pad, dtype=bool),
        np.float32(pack_w),
        np.float32(1.0 if strategy == "STRICT_SPREAD" else 0.0)))
    idx = np.asarray(jax.device_get(idx))[0, :B].astype(np.int64)
    ok = np.asarray(jax.device_get(ok))[0, :B].astype(bool)
    return idx, ok


def solve_tick_sharded(avail_t, total_t, demand_dev, counts,
                       accel_node_dev, accel_dev, spread_threshold,
                       cost, c_cap: int, n_pad: int, r_pad: int,
                       nnz_max: int, n_shards: int) -> dict:
    """Sharded runtime tick against device-resident sharded world
    state; returns the merged sparse assignment (``merge_packed``)."""
    import jax
    fn = _jit_sharded_solve_tick(c_cap, n_pad, r_pad, nnz_max, n_shards)
    rows = np.asarray(jax.block_until_ready(fn(
        avail_t, total_t, demand_dev, counts, accel_node_dev, accel_dev,
        np.float32(spread_threshold), cost)))
    return merge_packed(rows, nnz_max)


def merge_packed(rows: np.ndarray, nnz_max: int) -> dict:
    """Merge per-shard packed rows [n_shards, 2*nnz_max+3] into one
    sparse assignment.  idx values are already GLOBAL flat positions;
    (placed, ok) are replicated; nnz sums across shards."""
    idx_parts, val_parts = [], []
    for row in rows:
        k = int(np.rint(row[2 * nnz_max + 2]))
        k = max(0, min(k, nnz_max))
        idx_parts.append(np.rint(row[:k]).astype(np.int64))
        val_parts.append(row[nnz_max:nnz_max + k])
    return {
        "idx": np.concatenate(idx_parts) if idx_parts
        else np.zeros(0, np.int64),
        "vals": np.concatenate(val_parts) if val_parts
        else np.zeros(0, np.float32),
        "placed": float(rows[0, 2 * nnz_max]),
        "ok": bool(rows[0, 2 * nnz_max + 1] > 0.5),
        "nnz": int(sum(int(np.rint(r[2 * nnz_max + 2])) for r in rows)),
    }
