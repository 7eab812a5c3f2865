"""Pallas TPU kernel: parallel Double-VByte block decode.

TPU adaptation of the paper's byte-sequential decoder (§2.2/§3.4): a VMEM
tile of TB blocks × B bytes is decoded entirely in parallel on the VPU.

Per 8-bit lane:                               per-tile cost
  1. terminator flag       t = (b & 0x80)==0         1 cmp
  2. code starts           prev-terminator cummax    log2(B) shifted maxima
  3. payload shift         (b&0x7F) << 7*(pos-start) 1 shift
  4. value at terminator   cumsum difference         log2(B) shifted adds
  5. Algorithm 2 unfold    escape-pairing automaton  fori_loop over B lanes
                           (vectorized across the TB block rows)

Step 5 is the only sequential part and runs once per byte *position*, not per
byte — all blocks in the tile advance together, so the loop body is a fully
dense (TB,)-wide vector op.  This mirrors how SIMD varint decoders (e.g.
stream-vbyte) hoist the data-dependent control flow into masks.

The cummax/cumsum are implemented as unrolled log-step Hillis–Steele scans
(B is a compile-time constant, typically 64) because they vectorize on the
VPU without needing lax.associative_scan inside the kernel.

Block geometry (start = first payload byte, end = one-past-last) arrives as
two i32 vectors; everything outside [start, end) is masked, and the null
sentinel (§2.2) masks unused tail bytes automatically because a decoded
value of 0 cannot otherwise occur.

Outputs mirror the pure-jnp oracle ``ref.decode_blocks_ref``: (g, f, valid)
of shape (NB, B) — one potential posting per byte position.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_TILE = 256  # blocks per grid step: 256*64 B in + 3*256*64*4 B out


def _cummax(x: jnp.ndarray, axis: int) -> jnp.ndarray:
    """Unrolled Hillis–Steele inclusive running maximum along ``axis``."""
    n = x.shape[axis]
    shift = 1
    while shift < n:
        shifted = jnp.roll(x, shift, axis=axis)
        # zero out the wrapped-around prefix
        idx = jax.lax.broadcasted_iota(jnp.int32, x.shape, axis)
        shifted = jnp.where(idx >= shift, shifted, jnp.iinfo(jnp.int32).min)
        x = jnp.maximum(x, shifted)
        shift *= 2
    return x


def _cumsum(x: jnp.ndarray, axis: int) -> jnp.ndarray:
    """Unrolled Hillis–Steele inclusive prefix sum along ``axis``."""
    n = x.shape[axis]
    shift = 1
    while shift < n:
        shifted = jnp.roll(x, shift, axis=axis)
        idx = jax.lax.broadcasted_iota(jnp.int32, x.shape, axis)
        shifted = jnp.where(idx >= shift, shifted, 0)
        x = x + shifted
        shift *= 2
    return x


def _decode_tile(b_ref, start_ref, end_ref, g_ref, f_ref, v_ref, *, F: int):
    b = b_ref[...].astype(jnp.int32)           # (TB, B)
    TB, B = b.shape
    start = start_ref[...].reshape(TB, 1)
    end = end_ref[...].reshape(TB, 1)
    pos = jax.lax.broadcasted_iota(jnp.int32, (TB, B), 1)
    inside = (pos >= start) & (pos < end)
    term = ((b & 0x80) == 0) & inside
    # code start = previous terminator + 1 (clamped to the payload start)
    prev_term = _cummax(jnp.where(term, pos, -1), axis=1)
    code_start = jnp.maximum(
        jnp.where(pos > 0,
                  jnp.roll(prev_term, 1, axis=1), -1) + 1, start)
    pos_in_code = jnp.clip(pos - code_start, 0, 4)
    payload = jnp.where(inside, (b & 0x7F) << (7 * pos_in_code), 0)
    csum = _cumsum(payload, axis=1)
    # csum at (code_start - 1), via gather-free trick: since code_start-1 is
    # the previous terminator position, propagate csum-at-terminator forward.
    prev_csum = _cummax(  # runs of zeros take the last terminator's csum
        jnp.where(term, csum, jnp.iinfo(jnp.int32).min), axis=1)
    prev_csum = jnp.where(pos > 0, jnp.roll(prev_csum, 1, axis=1), 0)
    prev_csum = jnp.maximum(prev_csum, 0)  # head of row: nothing before
    value = jnp.where(term, csum - prev_csum, 0)
    is_value = term & (value > 0)
    mod = value % F

    # --- Algorithm 2 escape-pairing automaton over byte positions ---------
    # Pass 1 marks primaries/consumed columns; pass 2 (below, gather-free)
    # propagates each consumed escape value leftward onto its primary.
    prev_esc = jnp.zeros((TB,), jnp.bool_)
    g = jnp.zeros((TB, B), jnp.int32)
    f = jnp.zeros((TB, B), jnp.int32)
    prim = jnp.zeros((TB, B), jnp.bool_)
    cons = jnp.zeros((TB, B), jnp.bool_)

    def body2(i, carry):
        prev_esc, g, f, prim, cons = carry
        isv = is_value[:, i]
        v = value[:, i]
        m = mod[:, i]
        consumed = isv & prev_esc
        primary = isv & ~consumed
        esc_now = primary & (m == 0)
        gi = jnp.where(m > 0, 1 + v // F, v // F)
        fi = jnp.where(m > 0, m, 0)
        g = g.at[:, i].set(jnp.where(primary, gi, 0))
        f = f.at[:, i].set(jnp.where(primary, fi, 0))
        prim = prim.at[:, i].set(primary)
        cons = cons.at[:, i].set(consumed)
        return (jnp.where(isv, esc_now, prev_esc), g, f, prim, cons)

    _, g, f, prim, cons = jax.lax.fori_loop(
        0, B, body2, (prev_esc, g, f, prim, cons))
    # leftward propagation of each consumed value to its escape primary:
    # fpatch candidates live at consumed positions; reverse-cummax by column
    # index propagates the *nearest following* consumed value to the primary.
    fval = jnp.where(cons, F + value - 1, 0)
    # reverse scan: nearest non-zero to the right, log-step "hold last"
    rev = jnp.flip(fval, axis=1)
    run = rev
    shift = 1
    while shift < B:
        shifted = jnp.roll(run, shift, axis=1)
        idx = jax.lax.broadcasted_iota(jnp.int32, run.shape, 1)
        shifted = jnp.where(idx >= shift, shifted, 0)
        run = jnp.where(run > 0, run, shifted)
        shift *= 2
    nxt = jnp.flip(run, axis=1)
    f = jnp.where(prim & (f == 0), nxt, f)
    g_ref[...] = g
    f_ref[...] = f
    v_ref[...] = prim


def dvbyte_decode_kernel(blocks: jnp.ndarray, start: jnp.ndarray,
                         end: jnp.ndarray, F: int,
                         tile: int = DEFAULT_TILE, *, interpret: bool):
    """pallas_call wrapper: decode (NB, B) blocks, tiled TB rows at a time."""
    NB, B = blocks.shape
    if NB % tile != 0:
        pad = tile - NB % tile
        blocks = jnp.pad(blocks, ((0, pad), (0, 0)))
        start = jnp.pad(start, (0, pad))
        end = jnp.pad(end, (0, pad))
    NBp = blocks.shape[0]
    grid = (NBp // tile,)
    kern = functools.partial(_decode_tile, F=F)
    g, f, v = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile, B), lambda i: (i, 0)),
            pl.BlockSpec((tile,), lambda i: (i,)),
            pl.BlockSpec((tile,), lambda i: (i,)),
        ],
        out_specs=[
            pl.BlockSpec((tile, B), lambda i: (i, 0)),
            pl.BlockSpec((tile, B), lambda i: (i, 0)),
            pl.BlockSpec((tile, B), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((NBp, B), jnp.int32),
            jax.ShapeDtypeStruct((NBp, B), jnp.int32),
            jax.ShapeDtypeStruct((NBp, B), jnp.bool_),
        ],
        interpret=interpret,
    )(blocks, start, end)
    return g[:NB], f[:NB], v[:NB]
