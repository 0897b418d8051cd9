"""Batched Smith-Waterman local alignment.

Semantics match ``algorithms/smithwaterman/`` in the reference:
constant-gap scoring with the exact move-priority and tie-breaking of
``SmithWatermanGapScoringFromFn.buildScoringMatrix``
(B if m>=d && m>=in && m>0, else J if d>=in && d>0, else I if in>0,
else terminate) and ``SmithWaterman.maxCoordinates`` (on score ties the
*later* row/column wins, because the reference's fold keeps the right
operand on equality), and the same trackback emission
(B -> M/M, J -> I in x / D in y, I -> D in x / I in y).

TPU formulation: the O(|x|·|y|) matrix fill runs as an anti-diagonal
wavefront — each step updates a whole diagonal vector-wide, the pair
dimension is batched, and the matrices are *kept in diagonal layout*
``[B, D, lx+1]`` (``matrix[i, j] == diag[i + j, i]``) so no device-side
gather/transpose is ever paid.  Two interchangeable fills:

* :func:`_sw_fill_pallas` — Pallas TPU kernel: x/y codes and the two
  rolling diagonals live in VMEM, the y lane is read through a dynamic
  lane slice of the reversed-padded sequence, one fused VPU step per
  diagonal (the GCUPS path of BASELINE.md).
* :func:`_sw_fill_scan` — ``lax.scan`` fill, the default
  (:func:`sw_fill`).

Trackback is O(|x|+|y|) per pair on the host, reading the diagonal
move matrix directly.
"""

from __future__ import annotations

import functools
import os
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

# move codes in the device move matrix
MOVE_T = 0  # terminate
MOVE_B = 1  # both (diagonal)
MOVE_J = 2  # consume x only
MOVE_I = 3  # consume y only

_LANE = 128


def _round_up(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


# ------------------------------------------------------------- scan fill


@partial(jax.jit, static_argnames=("lx", "ly"))
def _sw_fill_scan(
    x_codes, x_len, y_codes, y_len, w_match, w_mismatch, w_insert, w_delete,
    lx: int, ly: int,
):
    """Diagonal-layout fill via lax.scan.

    Returns (scores [B, D, lx+1] f32, moves [B, D, lx+1] u8) with
    ``matrix[b, i, j] = out[b, i + j, i]``.
    """
    B = x_codes.shape[0]
    D = lx + ly + 1
    ii = jnp.arange(lx + 1)
    # f32 compute to match the Pallas kernel bit-for-bit (and the TPU VPU)
    w_match = jnp.float32(w_match)
    w_mismatch = jnp.float32(w_mismatch)
    w_insert = jnp.float32(w_insert)
    w_delete = jnp.float32(w_delete)

    def step(carry, d):
        d1, d2 = carry  # diagonals d-1 and d-2, each [B, lx+1] indexed by i
        jj = d - ii
        valid = (
            (ii >= 1)
            & (jj >= 1)
            & (ii[None, :] <= x_len[:, None])
            & (jj[None, :] <= y_len[:, None])
        )
        xc = x_codes[:, jnp.clip(ii - 1, 0, lx - 1)]
        yc = y_codes[:, jnp.clip(jj - 1, 0, ly - 1)]  # jj is batch-invariant
        sub = jnp.where(xc == yc, w_match, w_mismatch)

        def shift_i(v):  # v[i-1] with 0 at i=0
            return jnp.pad(v[:, :-1], ((0, 0), (1, 0)))

        m = shift_i(d2) + sub
        dd = shift_i(d1) + w_delete
        inn = d1 + w_insert

        take_b = (m >= dd) & (m >= inn) & (m > 0.0)
        take_j = ~take_b & (dd >= inn) & (dd > 0.0)
        take_i = ~take_b & ~take_j & (inn > 0.0)
        score = jnp.where(
            take_b, m, jnp.where(take_j, dd, jnp.where(take_i, inn, 0.0))
        )
        move = jnp.where(
            take_b,
            MOVE_B,
            jnp.where(take_j, MOVE_J, jnp.where(take_i, MOVE_I, MOVE_T)),
        ).astype(jnp.uint8)
        score = jnp.where(valid, score, 0.0)
        move = jnp.where(valid, move, MOVE_T)
        return (score, d1), (score, move)

    (_, _), (diag_scores, diag_moves) = jax.lax.scan(
        step,
        (
            jnp.zeros((B, lx + 1), jnp.float32),
            jnp.zeros((B, lx + 1), jnp.float32),
        ),
        jnp.arange(D),
    )
    # [D, B, L] -> [B, D, L]
    return (
        jnp.moveaxis(diag_scores, 0, 1).astype(jnp.float32),
        jnp.moveaxis(diag_moves, 0, 1),
    )


# ----------------------------------------------------------- pallas fill


def _per_lane_best(scores, x_len, y_len):
    """Per-lane (matrix row) running max over diagonals, ties -> last d.

    -> (best_sc f32[B, L] with -inf outside the valid region,
        best_d i32[B, L] diagonal index of the winning cell).
    Reducing over lanes with ties -> last lane reproduces the reference's
    maxCoordinates lexicographic-(i, j)-max rule (the right-biased fold
    in SmithWaterman.maxCoordinates, SmithWaterman.scala:50-83).
    """
    B, D, L = scores.shape
    ii = jnp.arange(L)[None, None, :]
    dd = jnp.arange(D)[None, :, None]
    jj = dd - ii
    valid = (
        (ii <= x_len[:, None, None])
        & (jj >= 0)
        & (jj <= y_len[:, None, None])
    )
    masked = jnp.where(valid, scores, -jnp.inf)
    amax_rev = jnp.argmax(masked[:, ::-1, :], axis=1)  # first max = last d
    best_d = (D - 1 - amax_rev).astype(jnp.int32)
    best_sc = jnp.max(masked, axis=1).astype(jnp.float32)
    return best_sc, best_d


@partial(jax.jit, static_argnames=("lx", "ly"))
def _sw_fill_scan_best(
    x_codes, x_len, y_codes, y_len, w_match, w_mismatch, w_insert, w_delete,
    lx: int, ly: int,
):
    """Scan fill + per-lane best, fused under one jit so the full f32
    score matrix never leaves the device."""
    scores, moves = _sw_fill_scan.__wrapped__(
        x_codes, x_len, y_codes, y_len,
        w_match, w_mismatch, w_insert, w_delete, lx, ly,
    )
    best_sc, best_d = _per_lane_best(scores, x_len, y_len)
    return moves, best_sc, best_d


def _sw_kernel(x_ref, ydiag_ref, xlen_ref, ylen_ref, move_ref,
               best_sc_ref, best_d_ref,
               d1_ref, d2_ref, *, lx: int, ly: int, L: int,
               w_match: float, w_mismatch: float, w_insert: float,
               w_delete: float):
    """One grid-less call fills all D diagonals of one TB-row batch tile.

    Mosaic constraints shape this kernel (all verified against the real
    TPU compile service):

    * No Pallas *grid* is used: this toolchain fails to legalize grids
      whose block index maps revisit a block (any spec that ignores a
      grid dimension), which a diagonal-in-grid layout would need for x
      and y.  Instead the diagonal loop is a ``fori_loop`` and the
      (D, TB, L) arrays are indexed on the *untiled* leading dimension,
      which lowers fine.
    * No unaligned dynamic lane slice — and a per-step ``pltpu.roll``
      measured ~0.3 ms/step — so the y lane windows for every diagonal
      are pre-gathered in XLA into ``ydiag[d, :, i] = y[d - 1 - i]``
      (i8) and the kernel just reads ``ydiag_ref[d]``.
    """
    TB = x_ref.shape[0]
    D = lx + ly + 1
    # all in-kernel scalars are pinned to i32/f32: under jax_enable_x64 a
    # bare Python literal becomes an i64/f64 constant, and Mosaic's
    # convert-element-type lowering recurses forever on 64-bit casts
    ii = jax.lax.broadcasted_iota(jnp.int32, (TB, L), 1)
    one = jnp.int32(1)
    zf = jnp.float32(0.0)
    wm = jnp.float32(w_match)
    wx = jnp.float32(w_mismatch)
    wi = jnp.float32(w_insert)
    wd = jnp.float32(w_delete)
    mv_b, mv_j, mv_i, mv_t = (
        jnp.int32(MOVE_B), jnp.int32(MOVE_J), jnp.int32(MOVE_I), jnp.int32(MOVE_T),
    )
    zero = jnp.int32(0)
    ninf = jnp.float32(-jnp.inf)
    xlen = xlen_ref[:]  # [TB, 1]
    ylen = ylen_ref[:]
    # xc: lane i holds x[i-1] (static shift; lane 0 and lanes past lx are
    # junk — masked by `valid`, and the -2 pad can never equal ydiag's -1).
    # Codes live as i32: i8 vectors carry (32, 128) tiling whose compare
    # masks Mosaic cannot relayout against the f32 selects.
    xc = jnp.pad(x_ref[:], ((0, 0), (1, L - 1 - lx)),
                 constant_values=jnp.int32(-2))
    d1_ref[:] = jnp.zeros((TB, L), jnp.float32)
    d2_ref[:] = jnp.zeros((TB, L), jnp.float32)
    best_sc_ref[:] = jnp.full((TB, L), ninf, jnp.float32)
    best_d_ref[:] = jnp.zeros((TB, L), jnp.int32)

    def body(d, c):
        jj = d - ii
        valid = (ii >= one) & (jj >= one) & (ii <= xlen) & (jj <= ylen)
        yc = ydiag_ref[d, :, :]
        sub = jnp.where(xc == yc, wm, wx)
        d1 = d1_ref[:]
        d2 = d2_ref[:]
        m = jnp.pad(d2[:, : L - 1], ((0, 0), (1, 0))) + sub
        dd = jnp.pad(d1[:, : L - 1], ((0, 0), (1, 0))) + wd
        inn = d1 + wi
        take_b = (m >= dd) & (m >= inn) & (m > zf)
        take_j = ~take_b & (dd >= inn) & (dd > zf)
        take_i = ~take_b & ~take_j & (inn > zf)
        score = jnp.where(
            take_b, m, jnp.where(take_j, dd, jnp.where(take_i, inn, zf))
        )
        score = jnp.where(valid, score, zf)
        move = jnp.where(
            take_b, mv_b, jnp.where(take_j, mv_j, jnp.where(take_i, mv_i, mv_t))
        )
        move = jnp.where(valid, move, mv_t)
        move_ref[d, :, :] = move.astype(jnp.int8)
        # running per-lane max over the valid region (incl. the zero
        # borders i==0 / j==0); ties -> later diagonal (larger j)
        in_region = (ii <= xlen) & (jj >= zero) & (jj <= ylen)
        cur = jnp.where(in_region, score, ninf)
        upd = cur >= best_sc_ref[:]
        best_sc_ref[:] = jnp.where(upd, cur, best_sc_ref[:])
        best_d_ref[:] = jnp.where(upd, d, best_d_ref[:])
        d2_ref[:] = d1
        d1_ref[:] = score
        return c

    jax.lax.fori_loop(jnp.int32(0), jnp.int32(D), body, jnp.int32(0))


@partial(
    jax.jit,
    static_argnames=(
        "lx", "ly", "w_match", "w_mismatch", "w_insert", "w_delete",
        "interpret",
    ),
)
def _sw_fill_pallas(
    x_codes, x_len, y_codes, y_len, lx: int, ly: int,
    w_match: float, w_mismatch: float, w_insert: float, w_delete: float,
    interpret: bool = False,
):
    """Pallas wavefront fill.

    -> (moves u8[B, D, lx+1], best_sc f32[B, lx+1], best_d i32[B, lx+1]),
    matching :func:`_sw_fill_scan_best` bit-for-bit.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B = x_codes.shape[0]
    D = lx + ly + 1
    L = _round_up(lx + 1, _LANE)
    # tile so the (D, TB, L) i8 move matrix + pre-gathered i32 y
    # diagonals fit comfortably in VMEM (~16MB/core); scores are never
    # materialized — the kernel tracks the per-lane running max instead
    TB = max(1, min(B, (2 * 1024 * 1024) // (D * L)))
    TB = _round_up(TB, 32)  # (32, 128) i8-tile-divisible batch tile
    Bp = _round_up(B, TB)

    x = jnp.full((Bp, lx), -2, jnp.int32).at[:B].set(x_codes.astype(jnp.int32))
    # ydiag[b, d, i] = y[b, d - 1 - i] (-1 outside the read): the
    # per-diagonal y lane windows, gathered once in XLA so the kernel
    # never needs an unaligned dynamic lane slice (or a per-step roll)
    ypad = jnp.full((Bp, lx + ly + L), -1, jnp.int32)
    ypad = ypad.at[:B, lx: lx + ly].set(y_codes[:, ::-1].astype(jnp.int32))
    widx = (lx + ly - jnp.arange(D))[:, None] + jnp.arange(L)[None, :]
    ydiag = ypad[:, widx]  # [Bp, D, L]
    xl = jnp.zeros((Bp, 1), jnp.int32).at[:B, 0].set(x_len.astype(jnp.int32))
    yl = jnp.zeros((Bp, 1), jnp.int32).at[:B, 0].set(y_len.astype(jnp.int32))

    kernel = functools.partial(
        _sw_kernel, lx=lx, ly=ly, L=L,
        w_match=w_match, w_mismatch=w_mismatch,
        w_insert=w_insert, w_delete=w_delete,
    )
    fill = pl.pallas_call(
        kernel,
        out_shape=[
            jax.ShapeDtypeStruct((D, TB, L), jnp.int8),
            jax.ShapeDtypeStruct((TB, L), jnp.float32),
            jax.ShapeDtypeStruct((TB, L), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((TB, L), jnp.float32),
            pltpu.VMEM((TB, L), jnp.float32),
        ],
        interpret=interpret,
    )

    nt = Bp // TB
    if nt == 1:
        m, bs, bd = fill(
            x, jnp.transpose(ydiag, (1, 0, 2)), xl, yl
        )  # [D, TB, L], [TB, L] x2
        moves = jnp.transpose(m, (1, 0, 2))
    else:
        # one compiled kernel, sequential over batch tiles
        m, bs, bd = jax.lax.map(
            lambda t: fill(*t),
            (
                x.reshape(nt, TB, lx),
                jnp.transpose(
                    ydiag.reshape(nt, TB, D, L), (0, 2, 1, 3)
                ),
                xl.reshape(nt, TB, 1),
                yl.reshape(nt, TB, 1),
            ),
        )  # [nt, D, TB, L], [nt, TB, L] x2
        moves = jnp.transpose(m, (0, 2, 1, 3)).reshape(Bp, D, L)
        bs = bs.reshape(Bp, L)
        bd = bd.reshape(Bp, L)
    return (
        moves[:B, :, : lx + 1].astype(jnp.uint8),
        bs[:B, : lx + 1],
        bd[:B, : lx + 1],
    )


# ----------------------------------------------------- score-only fills
#
# The GCUPS path (BASELINE metric 2).  Alignment *scores* need neither
# the [B, D, L] move matrix nor per-lane argmax bookkeeping — the row
# recurrence carries two [B, L] vectors and a running max.  The same-row
# delete chain H[i] = max(tmp[i], H[i-1] + wd) is solved by log2(L)
# doubling steps of static lane shifts (striped SW's prefix-max with
# linear decay), which both XLA and Mosaic vectorize cleanly — no
# per-diagonal y gathers, no unaligned dynamic lane slices.


@partial(jax.jit, static_argnames=("lx", "ly"))
def _sw_score_scan(
    x_codes, x_len, y_codes, y_len, w_match, w_mismatch, w_insert, w_delete,
    lx: int, ly: int,
):
    """Best local-alignment score per pair -> f32[B] (value-parity with
    :func:`_sw_fill_scan_best`'s best_sc max; i32/f32 throughout — i64
    vector ops are emulated on TPU)."""
    B = x_codes.shape[0]
    L = lx + 1
    wm = jnp.float32(w_match)
    wx = jnp.float32(w_mismatch)
    wi = jnp.float32(w_insert)
    wd = jnp.float32(w_delete)
    ii = jnp.arange(1, L, dtype=jnp.int32)  # lane i holds matrix row i
    in_x = ii[None, :] <= x_len.astype(jnp.int32)[:, None]
    xc = x_codes.astype(jnp.int32)  # lane i-1 holds x[i-1]
    yT = y_codes.astype(jnp.int32).T  # [ly, B]: scalar row per step

    shifts = []
    s = 1
    while s < L - 1:
        shifts.append(s)
        s *= 2

    def step(carry, args):
        # h_prev [B, lx+1]: lane i = matrix row i of the previous column
        h_prev, best = carry
        yj, jok = args  # y code [B], j <= y_len mask [B]
        sub = jnp.where(xc == yj[:, None], wm, wx)  # [B, lx], lane k = x[k]
        m = h_prev[:, :-1] + sub       # row i reads h_prev[i-1]
        inn = h_prev[:, 1:] + wi       # row i reads h_prev[i]
        tmp = jnp.maximum(jnp.maximum(m, inn), 0.0)
        # same-row delete chain H[i] = max(tmp[i], H[i-1] + wd) via
        # doubling (decay wd per lane step); the row-0 boundary (value 0)
        # never wins because tmp >= 0 > k*wd
        h = tmp
        for s in shifts:
            h = jnp.maximum(
                h,
                jnp.pad(h[:, :-s], ((0, 0), (s, 0)), constant_values=-jnp.inf)
                + jnp.float32(s) * wd,
            )
        h = jnp.where(in_x & jok[:, None], h, 0.0)
        # keep best as a [B, lx] accumulator — one elementwise max per
        # step instead of a per-step lane reduction; reduce once at end
        best = jnp.maximum(best, h)
        hfull = jnp.pad(h, ((0, 0), (1, 0)))  # prepend boundary row 0
        return (hfull, best), None

    h0 = jnp.zeros((B, L), jnp.float32)
    jok = (
        jnp.arange(1, ly + 1, dtype=jnp.int32)[:, None]
        <= y_len.astype(jnp.int32)[None, :]
    )
    (_, best2d), _ = jax.lax.scan(
        step, (h0, jnp.zeros((B, L - 1), jnp.float32)), (yT, jok)
    )
    return best2d.max(axis=1)


_SW_DTYPES = {"f32": jnp.float32, "bf16": jnp.bfloat16, "i32": jnp.int32}


def _sw_score_kernel(x_ref, y_ref, xmask_ref, ymask_ref, best_ref,
                     h_ref, *, lx: int, ly: int, L: int,
                     w_match: float, w_mismatch: float, w_insert: float,
                     w_delete: float, dtype_name: str = "f32"):
    """Mosaic kernel body for one batch tile, transposed layout.

    Arrays are [L, TB] — read position in SUBLANES, batch pair in LANES —
    so the per-step y row reads as a clean [1, TB] dynamic slice off the
    leading dimension and broadcasts against the [L, TB] state for free
    (a [TB, 1]-shaped slice tiles its size-1 minor dim out to 128 lanes
    in VMEM: 128x memory for nothing).  State (rolling column + running
    best) lives in VMEM; the same-row delete chain resolves with log2(L)
    static sublane shifts.

    ``dtype_name`` picks the compute element type: "f32" is the exact
    path for ADAM's fractional default weights
    (SmithWatermanConstantGapScoring.scala:20-43); "i32" requires
    integral weights (scores stay exact integers); "bf16" is
    measurement-only (integers above 256 round)."""
    from jax.experimental import pallas as pl

    dt = _SW_DTYPES[dtype_name]
    integral = dtype_name == "i32"
    wm = dt(w_match)
    wx = dt(w_mismatch)
    wi = dt(w_insert)
    zf = dt(0)
    # pad value for the delete-chain shifts: never wins (h >= 0); for
    # i32 it sits far enough above the type min that adding s*wd cannot
    # wrap
    ninf = dt(-16384) if integral else dt(-jnp.inf)
    xc = x_ref[:]  # [L, TB] i32, sublane i = x[i] (-2 padding)
    xmask = xmask_ref[:]  # [L, TB] 1/0 in dt: row i+1 <= x_len
    h_ref[:] = jnp.zeros_like(h_ref)
    best_ref[:] = jnp.zeros_like(best_ref)

    shifts = []
    s = 1
    while s < L:
        shifts.append(s)
        s *= 2

    def body(j, c):
        h_prev = h_ref[:]  # sublane i holds H[row i+1] of previous column
        yj = y_ref[pl.ds(j, 1), :]  # [1, TB] i32
        jok = ymask_ref[pl.ds(j, 1), :]  # [1, TB] f32 1/0
        sub = jnp.where(xc == yj, wm, wx)
        hp_shift = jnp.pad(h_prev[: L - 1, :], ((1, 0), (0, 0)))
        m = hp_shift + sub
        inn = h_prev + wi
        tmp = jnp.maximum(jnp.maximum(m, inn), zf)
        h = tmp
        for s in shifts:
            # python-level product, cast once: bf16/f32 stay in their
            # own type (a f32 scalar would promote the whole chain)
            decay = dt(s * w_delete) if integral else dt(
                np.float32(s) * np.float32(w_delete)
            )
            h = jnp.maximum(
                h,
                jnp.pad(h[: L - s, :], ((s, 0), (0, 0)),
                        constant_values=ninf) + decay,
            )
        h = jnp.maximum(h, zf)
        h = h * xmask * jok
        h_ref[:] = h
        best_ref[:] = jnp.maximum(best_ref[:], h)
        return c

    jax.lax.fori_loop(jnp.int32(0), jnp.int32(ly), body, jnp.int32(0))


@partial(
    jax.jit,
    static_argnames=(
        "lx", "ly", "w_match", "w_mismatch", "w_insert", "w_delete",
        "interpret", "dtype_name",
    ),
)
def _sw_score_pallas(
    x_codes, x_len, y_codes, y_len, lx: int, ly: int,
    w_match: float, w_mismatch: float, w_insert: float, w_delete: float,
    interpret: bool = False, dtype_name: str = "f32",
):
    """Pallas striped score fill -> f32[B] best scores."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    dt = _SW_DTYPES[dtype_name]
    if dtype_name == "i32":
        for w in (w_match, w_mismatch, w_insert, w_delete):
            if not float(w).is_integer():
                raise ValueError(
                    f"integer SW dtype {dtype_name} needs integral "
                    f"weights, got {w}"
                )
    B = x_codes.shape[0]
    L = _round_up(lx, _LANE)
    TB = max(_LANE, min(_round_up(B, _LANE), 1024))
    Bp = _round_up(B, TB)

    # transposed layout (see kernel docstring): [L, Bp] with batch in
    # lanes; sublane i holds x[i] (the kernel's row i+1); -2 never
    # matches y codes
    x = jnp.full((L, Bp), -2, jnp.int32).at[:lx, :B].set(
        x_codes.astype(jnp.int32).T
    )
    xmask = (
        jnp.arange(1, L + 1, dtype=jnp.int32)[:, None]
        <= jnp.zeros((1, Bp), jnp.int32).at[0, :B].set(
            x_len.astype(jnp.int32)
        )
    ).astype(dt)
    yT = jnp.full((ly, Bp), -1, jnp.int32).at[:, :B].set(
        y_codes.astype(jnp.int32).T
    )
    ymask = (
        jnp.arange(1, ly + 1, dtype=jnp.int32)[:, None]
        <= jnp.zeros((1, Bp), jnp.int32).at[0, :B].set(
            y_len.astype(jnp.int32)
        )
    ).astype(dt)

    kernel = functools.partial(
        _sw_score_kernel, lx=lx, ly=ly, L=L,
        w_match=w_match, w_mismatch=w_mismatch,
        w_insert=w_insert, w_delete=w_delete, dtype_name=dtype_name,
    )
    nt = Bp // TB
    # one pallas_call with a grid over batch (lane) tiles — each grid
    # step owns a distinct output block, the Mosaic-legal grid shape:
    # the runtime pipelines tile i+1's HBM->VMEM copies under tile i's
    # compute, and the whole batch is a single dispatch instead of nt
    # sequential kernel launches
    fill = pl.pallas_call(
        kernel,
        grid=(nt,),
        in_specs=[
            pl.BlockSpec((L, TB), lambda i: (0, i)),
            pl.BlockSpec((ly, TB), lambda i: (0, i)),
            pl.BlockSpec((L, TB), lambda i: (0, i)),
            pl.BlockSpec((ly, TB), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((L, TB), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((L, Bp), dt),
        scratch_shapes=[pltpu.VMEM((L, TB), dt)],
        interpret=interpret,
    )
    # under jax_enable_x64 the grid machinery traces i64 indices, which
    # Mosaic fails to legalize ("func.return (i32, i64)"); every dtype in
    # this kernel is explicit, so tracing the call with x64 off is
    # semantics-preserving
    if jax.config.jax_enable_x64:
        with jax.enable_x64(False):
            best = fill(x, yT, xmask, ymask)
    else:
        best = fill(x, yT, xmask, ymask)
    return best.max(axis=0)[:B].astype(jnp.float32)


def sw_best_scores(
    x_codes, x_len, y_codes, y_len,
    w_match: float = 1.0, w_mismatch: float = -0.333,
    w_insert: float = -0.5, w_delete: float = -0.5,
    backend: str | None = None,
):
    """Best local-alignment score per pair (no trackback) -> f32[B]."""
    lx = int(np.shape(x_codes)[1])
    ly = int(np.shape(y_codes)[1])
    be = backend or os.environ.get("ADAM_TPU_SW_BACKEND", "scan")
    if be == "pallas":
        # the f32 kernel: exact for ADAM's fractional default weights
        return _sw_score_pallas(
            jnp.asarray(x_codes), jnp.asarray(x_len), jnp.asarray(y_codes),
            jnp.asarray(y_len), lx, ly,
            float(w_match), float(w_mismatch), float(w_insert),
            float(w_delete),
        )
    return _sw_score_scan(
        jnp.asarray(x_codes), jnp.asarray(x_len), jnp.asarray(y_codes),
        jnp.asarray(y_len), w_match, w_mismatch, w_insert, w_delete, lx, ly,
    )


def benchmark_gcups(
    B: int = 8192, lx: int = 127, ly: int = 127, reps: int = 6,
    backend: str | None = None, trials: int = 3,
) -> float:
    """Measured score-only fill throughput in GCUPS (giga cell updates
    per second), the standard Smith-Waterman metric (scores, no
    trackback — matching how SW search tools report GCUPS).

    The repetition loop runs on device inside one jit with a data
    dependency chained between fills (each rep's x is perturbed by a
    value derived from the previous best scores), so reps cannot be
    collapsed, and the final scalar is fetched once.  Returns the best
    of ``trials`` timed runs.
    """
    import time

    rng = np.random.default_rng(0)
    xc = jnp.asarray(rng.integers(0, 4, (B, lx)), jnp.int32)
    yc = jnp.asarray(rng.integers(0, 4, (B, ly)), jnp.int32)
    xl = jnp.full((B,), lx, jnp.int32)
    yl = jnp.full((B,), ly, jnp.int32)
    args = (1.0, -0.333, -0.5, -0.5)

    @jax.jit
    def bench(xc0):
        def body(i, carry):
            x, acc = carry
            best = sw_best_scores(x, xl, yc, yl, *args, backend=backend)
            # data dependency: perturb x by a (always-zero) value derived
            # from this rep's result, so reps can't be collapsed/memoized
            x = x + (best[0:1, None] % 1).astype(x.dtype)
            return (x, acc + best.sum())

        return jax.lax.fori_loop(0, reps, body, (xc0, jnp.float32(0)))[1]

    acc = bench(xc)
    jax.block_until_ready(acc)  # compile + warm
    best_dt = float("inf")
    for t in range(max(1, trials)):
        t0 = time.perf_counter()
        acc = bench(xc + jnp.int32(t) - jnp.int32(t))
        float(acc)  # full sync
        best_dt = min(best_dt, (time.perf_counter() - t0) / reps)
    return B * lx * ly / best_dt / 1e9


def sw_fill(x_codes, x_len, y_codes, y_len, w_match, w_mismatch, w_insert,
            w_delete, lx: int, ly: int):
    """Diagonal-layout fill for the trackback path.

    -> (moves u8[B, D, lx+1], best_sc f32[B, lx+1], best_d i32[B, lx+1]).

    The ``lax.scan`` fill is the default on every backend: it
    materializes the [B, D, L] move matrix the host trackback needs,
    and XLA pipelines that fine.  ``ADAM_TPU_SW_BACKEND=pallas`` runs
    the Pallas wavefront kernel instead (bit-identical; compiled on the
    chip by ``chip_smoke.py`` and for a described v5e by
    ``tests/test_chip_compile.py``).  The score-only fills above are
    the GCUPS path; a cell update there costs ~20 vector max/add ops
    (the log2(L)-step delete chain dominates), so it is VPU-bound and
    the MXU cannot help.
    """
    if os.environ.get("ADAM_TPU_SW_BACKEND", "scan") == "pallas":
        return _sw_fill_pallas(
            jnp.asarray(x_codes), jnp.asarray(x_len),
            jnp.asarray(y_codes), jnp.asarray(y_len), lx, ly,
            float(w_match), float(w_mismatch), float(w_insert),
            float(w_delete),
        )
    return _sw_fill_scan_best(
        jnp.asarray(x_codes), jnp.asarray(x_len), jnp.asarray(y_codes),
        jnp.asarray(y_len), w_match, w_mismatch, w_insert, w_delete, lx, ly,
    )


# ------------------------------------------------------------ trackback


@dataclass(frozen=True)
class SWAlignment:
    cigar_x: str
    cigar_y: str
    x_start: int
    y_start: int
    x_end: int  # exclusive end of the aligned span in x
    y_end: int
    score: float


def _max_coordinates(
    best_sc: np.ndarray, best_d: np.ndarray, x_len: int
) -> tuple[int, int, float]:
    """Reference tie rule from the per-lane best arrays: the global max
    with the LAST row i winning ties, then the LAST column j
    (maxCoordinates' right-biased fold; the per-lane max already kept
    the largest diagonal = largest j within each row)."""
    lanes = best_sc[: x_len + 1]
    best = lanes.max()
    i = int(np.flatnonzero(lanes == best).max())
    j = int(best_d[i]) - i
    return i, j, float(best)


def _rnn_to_cigar(ops: list[str]) -> str:
    """Reversed unit-length op list -> run-length CIGAR string."""
    if not ops:
        return ""
    out = []
    last, run = ops[0], 1
    for c in ops[1:]:
        if c == last:
            run += 1
        else:
            out.append(f"{run}{last}")
            last, run = c, 1
    out.append(f"{run}{last}")
    return "".join(reversed(out))


def _trackback(
    diag_moves: np.ndarray, best_sc: np.ndarray, best_d: np.ndarray,
    x_len: int,
) -> SWAlignment:
    i, j, score = _max_coordinates(best_sc, best_d, x_len)
    end_i, end_j = i, j
    cx: list[str] = []
    cy: list[str] = []
    while diag_moves[i + j, i] != MOVE_T:
        mv = diag_moves[i + j, i]
        if mv == MOVE_B:
            cx.append("M")
            cy.append("M")
            i -= 1
            j -= 1
        elif mv == MOVE_J:
            cx.append("I")
            cy.append("D")
            i -= 1
        else:
            cx.append("D")
            cy.append("I")
            j -= 1
    return SWAlignment(
        cigar_x=_rnn_to_cigar(cx),
        cigar_y=_rnn_to_cigar(cy),
        x_start=i,
        y_start=j,
        x_end=end_i,
        y_end=end_j,
        score=score,
    )


def smith_waterman_batch(
    x_codes,
    x_len,
    y_codes,
    y_len,
    w_match: float = 1.0,
    w_mismatch: float = -0.333,
    w_insert: float = -0.5,
    w_delete: float = -0.5,
) -> list[SWAlignment]:
    """Align each x[i] against y[i]; device fill + host trackback."""
    x_codes = jnp.asarray(x_codes)
    y_codes = jnp.asarray(y_codes)
    moves, best_sc, best_d = sw_fill(
        x_codes, jnp.asarray(x_len), y_codes, jnp.asarray(y_len),
        w_match, w_mismatch, w_insert, w_delete,
        int(x_codes.shape[1]), int(y_codes.shape[1]),
    )
    moves = np.asarray(moves)
    best_sc = np.asarray(best_sc)
    best_d = np.asarray(best_d)
    xl = np.asarray(x_len)
    return [
        _trackback(moves[b], best_sc[b], best_d[b], int(xl[b]))
        for b in range(x_codes.shape[0])
    ]


def smith_waterman(
    x: str,
    y: str,
    w_match: float = 1.0,
    w_mismatch: float = -0.333,
    w_insert: float = -0.5,
    w_delete: float = -0.5,
) -> SWAlignment:
    """Single-pair convenience wrapper (strings in, CIGARs out)."""
    from adam_tpu.formats.schema import encode_bases

    xc = encode_bases(x)[None, :]
    yc = encode_bases(y)[None, :]
    return smith_waterman_batch(
        xc, np.array([len(x)]), yc, np.array([len(y)]),
        w_match, w_mismatch, w_insert, w_delete,
    )[0]
