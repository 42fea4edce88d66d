"""Attention masks as small static descriptions.

A mask is a frozen (hashable) object, so it can be a static argument of
a jitted call.  From it the kernels get three things:

  * ``allowed(q_pos, k_pos)`` -- the elementwise predicate, on arrays of
    positions that broadcast against each other;
  * ``tile_span(seq_len)`` -- the length that a tile has to divide
    (the sequence, or the part of it that tiles must not straddle);
  * ``k_ranges(q_tile, block_q, block_k, num_k)`` for the forward (a
    grid over Q tiles) and ``q_ranges(k_tile, block_q, block_k, num_q)``
    for the backward (a grid over K tiles): the ranges of opposite tiles
    to visit, ``[(first, stop, masked), ...]``.  ``masked`` is a Python
    bool: the tiles of the range need the elementwise predicate; the
    others hold no disallowed pair.  ``first``/``stop`` are Python ints
    or traced scalars (the tile index is a ``program_id`` in a kernel).
    A tile with no allowed pair is in no range.

``full_attention`` (the jnp fallback) takes the same objects and uses
the predicate alone.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp


def _cdiv(a, b: int):
    return (a + b - 1) // b


def _block_of(pos, block: int):
    """``pos // block`` for positions >= 0: a shift where it can be."""
    if block & (block - 1) == 0:
        return jnp.right_shift(pos, block.bit_length() - 1)
    return jax.lax.div(pos, jnp.asarray(block, jnp.asarray(pos).dtype))


@dataclasses.dataclass(frozen=True)
class Full:
    """Every query sees every key."""

    def allowed(self, q_pos, k_pos):
        return jnp.ones(jnp.broadcast_shapes(jnp.shape(q_pos),
                                             jnp.shape(k_pos)), bool)

    def tile_span(self, seq_len: int) -> int:
        return seq_len

    def k_ranges(self, q_tile, block_q, block_k, num_k):
        return [(0, num_k, False)]

    def q_ranges(self, k_tile, block_q, block_k, num_q):
        return [(0, num_q, False)]


@dataclasses.dataclass(frozen=True)
class Causal:
    """Query ``i`` sees keys ``j <= i``."""

    def allowed(self, q_pos, k_pos):
        return q_pos >= k_pos

    def tile_span(self, seq_len: int) -> int:
        return seq_len

    def k_ranges(self, q_tile, block_q, block_k, num_k):
        # K tiles [0, stop) have a key at or before the tile's last row;
        # below ``unmasked`` every key is at or before its first row.
        unmasked = (q_tile * block_q + 1) // block_k
        stop = jnp.minimum(_cdiv((q_tile + 1) * block_q, block_k), num_k)
        return [(0, unmasked, False), (unmasked, stop, True)]

    def q_ranges(self, k_tile, block_q, block_k, num_q):
        # Q tiles [first, num_q) have a row at or past the tile's first
        # key; from ``unmasked`` on every row is past its last key.
        first = (k_tile * block_k) // block_q
        unmasked = jnp.minimum(_cdiv((k_tile + 1) * block_k - 1, block_q),
                               num_q)
        return [(first, unmasked, True), (unmasked, num_q, False)]


@dataclasses.dataclass(frozen=True)
class BlockDiffusion:
    """The training mask of block diffusion (BD3-LM) over ``2 * seq_len``
    positions ``[noisy ; clean]`` cut into blocks of ``block``.  With
    ``b(i) = (i mod seq_len) // block``:

        noisy query, noisy key:  b(i) == b(j)   (its own block, both ways)
        noisy query, clean key:  b(j) <  b(i)   (the clean blocks before)
        clean query, clean key:  b(j) <= b(i)   (causal by blocks)
        clean query, noisy key:  never

    ``seq_len * (seq_len + block)`` of the ``4 * seq_len**2`` pairs.
    """
    seq_len: int
    block: int

    def __post_init__(self):
        if self.seq_len % self.block:
            raise ValueError(f"block {self.block} does not divide the "
                             f"sequence length {self.seq_len}")

    def allowed(self, q_pos, k_pos):
        L, B = self.seq_len, self.block
        q_noisy, k_noisy = q_pos < L, k_pos < L
        qb = _block_of(jnp.where(q_noisy, q_pos, q_pos - L), B)
        kb = _block_of(jnp.where(k_noisy, k_pos, k_pos - L), B)
        # (logical operations only: Mosaic has no select between masks)
        return (k_noisy & q_noisy & (qb == kb)) | (
            ~k_noisy & ((kb < qb) | (~q_noisy & (kb == qb))))

    def tile_span(self, seq_len: int) -> int:
        """Tiles must not straddle the two halves."""
        if seq_len != 2 * self.seq_len:
            raise ValueError(f"the mask is over {2 * self.seq_len} "
                             f"positions, the operands have {seq_len}")
        return self.seq_len

    def _check(self, *tiles) -> None:
        """A tile is either whole blocks or a part of one block: then the
        noisy square is one range of tiles, all masked or all unmasked."""
        for tile in tiles:
            if tile % self.block and self.block % tile:
                raise ValueError(
                    f"a tile of {tile} must be a multiple or a divisor of "
                    f"the block length {self.block}")

    def _noisy_square_masked(self, block_q, block_k) -> bool:
        # A tile pair inside the noisy square holds a disallowed pair
        # unless both tiles lie within one block.
        return bool(self.block % block_q or self.block % block_k)

    def k_ranges(self, q_tile, block_q, block_k, num_k):
        self._check(block_q, block_k)
        L, B = self.seq_len, self.block
        half = L // block_k                       # K tiles a half
        noisy = q_tile * block_q < L
        q0 = jnp.where(noisy, q_tile * block_q, q_tile * block_q - L)
        b_first, b_last = q0 // B, (q0 + block_q - 1) // B
        # noisy keys: the blocks of this tile's rows (noisy rows only)
        n_lo = jnp.where(noisy, (b_first * B) // block_k, 0)
        n_hi = jnp.where(noisy, _cdiv((b_last + 1) * B, block_k), 0)
        # clean keys: every row sees blocks below ``all``; some row sees
        # blocks below ``any`` (a clean row sees its own block too)
        own = jnp.where(noisy, 0, 1)
        c_all = ((b_first + own) * B) // block_k
        c_any = jnp.maximum(_cdiv((b_last + own) * B, block_k), c_all)
        return [(n_lo, n_hi, self._noisy_square_masked(block_q, block_k)),
                (half, half + c_all, False),
                (half + c_all, half + c_any, True)]

    def q_ranges(self, k_tile, block_q, block_k, num_q):
        self._check(block_q, block_k)
        L, B = self.seq_len, self.block
        half = L // block_q                       # Q tiles a half
        noisy = k_tile * block_k < L
        k0 = jnp.where(noisy, k_tile * block_k, k_tile * block_k - L)
        b_first, b_last = k0 // B, (k0 + block_k - 1) // B
        # a noisy key is seen by the noisy rows of its own block
        own_lo = (b_first * B) // block_q
        own_hi = _cdiv((b_last + 1) * B, block_q)
        # a clean key of block b is seen by noisy rows of blocks > b ...
        n_any = jnp.minimum(((b_first + 1) * B) // block_q, half)
        n_all = jnp.minimum(_cdiv((b_last + 1) * B, block_q), half)
        # ... and by clean rows of blocks >= b
        c_any = (b_first * B) // block_q
        c_all = jnp.minimum(_cdiv(b_last * B, block_q), half)
        none = (0, 0)

        def pick(if_noisy, if_clean):
            return tuple(jnp.where(noisy, a, b)
                         for a, b in zip(if_noisy, if_clean))

        own_masked = self._noisy_square_masked(block_q, block_k)
        own = (own_lo, own_hi)
        return [
            pick(own if own_masked else none, (n_any, n_all)) + (True,),
            pick(none if own_masked else own, (n_all, half)) + (False,),
            pick(none, (half + c_any, half + c_all)) + (True,),
            pick(none, (half + c_all, 2 * half)) + (False,),
        ]


@dataclasses.dataclass(frozen=True)
class SlidingWindow:
    """Query ``i`` sees keys ``i - window < j <= i``: causal within its
    last ``window`` positions, its own among them.  A band of tiles a
    Q tile (or a K tile): those the causal diagonal crosses and those
    the window's far edge crosses are masked, what lies between is not
    (at tiles as long as the window nothing lies between)."""
    window: int

    def __post_init__(self):
        if self.window < 1:
            raise ValueError(f"a window of {self.window} positions")

    def allowed(self, q_pos, k_pos):
        return (q_pos >= k_pos) & (q_pos - k_pos < self.window)

    def tile_span(self, seq_len: int) -> int:
        return seq_len

    def k_ranges(self, q_tile, block_q, block_k, num_k):
        q0, q1 = q_tile * block_q, (q_tile + 1) * block_q
        # K tiles [first, stop) hold a key some row sees; in [inner,
        # unmasked) every row sees every key: the tile's last key is at
        # or before the first row, its first within the last row's window
        first = jnp.maximum(q0 - self.window + 1, 0) // block_k
        stop = jnp.minimum(_cdiv(q1, block_k), num_k)
        inner = jnp.clip(_cdiv(jnp.maximum(q1 - self.window, 0), block_k),
                         first, stop)
        unmasked = jnp.clip((q0 + 1) // block_k, inner, stop)
        return [(first, inner, True), (inner, unmasked, False),
                (unmasked, stop, True)]

    def q_ranges(self, k_tile, block_q, block_k, num_q):
        k0, k1 = k_tile * block_k, (k_tile + 1) * block_k
        # Q tiles [first, stop) hold a row that sees some key of the
        # tile; in [inner, unmasked) every row sees every key
        first = k0 // block_q
        stop = jnp.minimum(_cdiv(k1 + self.window - 1, block_q), num_q)
        inner = jnp.clip(_cdiv(k1 - 1, block_q), first, stop)
        unmasked = jnp.clip((k0 + self.window) // block_q, inner, stop)
        return [(first, inner, True), (inner, unmasked, False),
                (unmasked, stop, True)]


CAUSAL = Causal()
FULL = Full()
