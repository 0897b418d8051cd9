"""GATK-style local indel realignment.

Faithful semantics of the reference's ``rdd/read/realignment/`` +
``algorithms/consensus/`` packages, re-shaped for TPU:

1. **Target discovery** (RealignmentTargetFinder.scala:99-121,
   IndelRealignmentTarget.scala:108-143): every I/D CIGAR op (length <=
   maxIndelSize) yields a target (variation region, read span); targets
   sort by read span, merge while overlapping (variation hulls), dedupe
   on equal read spans (TreeSet semantics) and drop spans >
   maxTargetSize.  Here target extraction is a vectorized walk over the
   cigar columns.
2. **Read -> target mapping** (RealignIndels.mapToTarget:72-94): the
   reference's recursive set-halving search, including its exact pruning
   rule and the empty-target skew split ``-1 - start/3000``; vectorized
   so all reads binary-search simultaneously.
3. **Per-target realignment** (RealignIndels.realignTargetGroup:235-387):
   rebuild the reference from MD tags, left-normalize single-indel reads,
   take each indel read's alternate consensus (Consensus.scala:25-70),
   sweep every read over every consensus, accept the best consensus when
   the LOD improvement ((old-new)/10) beats the threshold, and rewrite
   start/CIGAR/MD (+10 mapq, OC/OP provenance tags).
4. The O(|reads| x |offsets| x |readLen|) **sweep**
   (sweepReadOverReferenceForQuality:399-417) is the hot loop: here it is
   one batched device kernel — mismatch-quality(b, o) = totalQual(b) -
   match-correlation(b, o), computed as a per-pair one-hot conv
   (MXU-shaped) over all (read, consensus) pairs of all targets at once.
"""

from __future__ import annotations

import logging
import os
import random
from dataclasses import dataclass, replace as dc_replace
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from adam_tpu.api.datasets import AlignmentDataset
from adam_tpu.formats import schema
from adam_tpu.models.snp_table import IndelTable
from adam_tpu.ops.mdtag import MdTag, parse_cigar
from adam_tpu.utils import telemetry as _tele

MAX_INDEL_SIZE = 500
MAX_CONSENSUS_NUMBER = 30
LOD_THRESHOLD = 5.0
MAX_TARGET_SIZE = 3000


# --------------------------------------------------------------------------
# CIGAR list helpers (host)
# --------------------------------------------------------------------------
def cigar_to_string(elems: list[tuple[int, str]]) -> str:
    return "".join(f"{n}{op}" for n, op in elems)


def cigar_read_len(elems) -> int:
    return sum(n for n, op in elems if op in "MIS=X")


def cigar_ref_len(elems) -> int:
    return sum(n for n, op in elems if op in "MDN=X")


def cigar_num_alignment_blocks(elems) -> int:
    return sum(1 for _, op in elems if op == "M")


def _cigar_total_len(elems) -> int:
    """Sum of ALL element lengths (RichCigar.getLength — includes D)."""
    return sum(n for n, _ in elems)


def move_cigar_left(elems: list[tuple[int, str]], index: int):
    """RichCigar.moveLeft semantics (rich/RichCigar.scala:140-186):
    trim one base from the element before ``index``, grow (or create, as
    1M) the element after it.  Replicates the reference's slicing,
    including dropping a 4th element when exactly 4 remain after the
    indel context."""
    if index == 0 or len(elems) < 2:
        return list(elems)
    head = list(elems[: index - 1])
    rest = list(elems[index - 1 :])
    trim = rest[0]
    move = rest[1] if len(rest) > 1 else None
    pad = rest[2] if len(rest) > 2 else None
    after_pad = rest[3:] if len(rest) > 4 else []
    out = list(head)
    if trim[0] > 1:
        out.append((trim[0] - 1, trim[1]))
    if move is not None:
        out.append(move)
    if pad is not None:
        out.append((pad[0] + 1, pad[1]))
    else:
        out.append((1, "M"))
    out += after_pad
    return out


def shift_indel(elems, position: int, shifts: int):
    """NormalizationUtils.shiftIndel (:142-153).

    The reference's well-formedness guard only compares total element
    length (RichCigar.isWellFormed:123-125 against the OLD total), so
    once the element before the indel is fully consumed, further moves
    start trimming the indel itself — the total can stay equal while the
    READ span (S+M+I) grows, and the reference then crashes in
    MdTag.moveAlignment on the out-of-range read index (a walk its
    suite never reaches; observed here on WGS-shaped data as an M span
    overrunning the read).  We additionally pin the read span AND the
    reference span, declining the corrupting move instead of
    reproducing the crash: a trimmed deletion changes the read span at
    constant total, while a trimmed insertion keeps both total and read
    span and silently erases the indel into M, growing the reference
    walk (tests: test_shift_indel_declines_read_length_corruption /
    _insertion_erasure)."""

    cur = list(elems)
    total = _cigar_total_len(cur)
    rlen = cigar_read_len(cur)
    reflen = cigar_ref_len(cur)
    while True:
        new = move_cigar_left(cur, position)
        if (
            shifts == 0
            or _cigar_total_len(new) != total
            or cigar_read_len(new) != rlen
            or cigar_ref_len(new) != reflen
        ):
            return cur
        cur = new
        shifts -= 1


def positions_to_shift(variant: str, preceding: str) -> int:
    """NormalizationUtils.numberOfPositionsToShiftIndel (:115-131)."""
    acc = 0
    v, p = variant, preceding
    while p and v and p[-1] == v[-1]:
        v = v[-1] + v[:-1]
        p = p[:-1]
        acc += 1
    return acc


def left_align_indel(seq: str, cigar: list, md: Optional[MdTag]):
    """NormalizationUtils.leftAlignIndel (:35-100): shift the single indel
    left through repeated sequence.  Returns a new cigar list."""
    indel_pos = -1
    indel_len = 0
    read_pos = ref_pos = 0
    is_insert = False
    for pos, (n, op) in enumerate(cigar):
        if op == "I":
            if indel_pos != -1:
                return list(cigar)
            indel_pos, indel_len, is_insert = pos, n, True
        elif op == "D":
            if indel_pos != -1:
                return list(cigar)
            indel_pos, indel_len = pos, n
        else:
            if indel_pos == -1:
                if op in "MIS=X":
                    read_pos += n
                if op in "MDN=X":
                    ref_pos += n
    if indel_pos == -1:
        return list(cigar)
    if is_insert:
        variant = seq[read_pos : read_pos + indel_len]
    else:
        if md is None:
            return list(cigar)
        ref = md.get_reference(seq, cigar_to_string(cigar))
        variant = ref[ref_pos : ref_pos + indel_len]
    preceding = seq[:read_pos]
    shift = positions_to_shift(variant, preceding)
    return shift_indel(cigar, indel_pos, shift)


# --------------------------------------------------------------------------
# Targets
# --------------------------------------------------------------------------
@dataclass
class RealignmentTarget:
    contig_idx: int
    var_start: int  # -1/-1 when no variation
    var_end: int
    range_start: int
    range_end: int

    @property
    def has_variation(self) -> bool:
        return self.var_start >= 0


def extract_indel_event_arrays(
    b, max_indel_size: int = MAX_INDEL_SIZE
) -> np.ndarray:
    """Per-read I/D events as an ``[n_events, 5]`` i64 array of
    (contig_idx, var_start, var_end, range_start, range_end) — no
    per-event Python objects (the WGS-scale hot path; ~13%% of reads
    carry an indel, so object churn here cost seconds per 1M reads).

    Event order matches the object path: column-major over the cigar
    slots, insertions then deletions per column, row-ascending."""
    n, C = b.cigar_ops.shape
    ops = np.asarray(b.cigar_ops)
    lens = np.asarray(b.cigar_lens).astype(np.int64)
    flags = np.asarray(b.flags)
    active = np.asarray(b.valid) & ((flags & schema.FLAG_UNMAPPED) == 0)
    starts = np.asarray(b.start).astype(np.int64)
    ends = np.asarray(b.end).astype(np.int64)
    contigs = np.asarray(b.contig_idx).astype(np.int64)
    # reference position at each cigar slot = start + exclusive cumsum of
    # ref-consuming op lengths
    r_consume = schema.CIGAR_CONSUMES_REF[np.minimum(ops, 15)].astype(np.int64)
    ref_adv = lens * r_consume
    ref_at = starts[:, None] + np.cumsum(ref_adv, axis=1) - ref_adv
    parts = []
    for k in range(C):
        op = ops[:, k]
        ln = lens[:, k]
        for is_ins in (True, False):
            code = schema.CIGAR_I if is_ins else schema.CIGAR_D
            rows = np.flatnonzero(
                active & (op == code) & (ln <= max_indel_size)
            )
            if not len(rows):
                continue
            vs = ref_at[rows, k]
            ve = vs + 1 if is_ins else vs + ln[rows]
            parts.append(np.stack(
                [contigs[rows], vs, ve, starts[rows], ends[rows]], axis=1
            ))
    if not parts:
        return np.zeros((0, 5), np.int64)
    return np.concatenate(parts, axis=0)


def extract_indel_events(
    b, max_indel_size: int = MAX_INDEL_SIZE
) -> list[RealignmentTarget]:
    """Per-read I/D targets (IndelRealignmentTarget.apply) as objects —
    the array form (:func:`extract_indel_event_arrays`) is the hot
    path; this wrapper exists for API/test compatibility."""
    ev = extract_indel_event_arrays(b, max_indel_size)
    return [
        RealignmentTarget(int(c), int(vs), int(ve), int(rs), int(re))
        for c, vs, ve, rs, re in ev.tolist()
    ]


def find_targets(
    ds: AlignmentDataset,
    max_target_size: int = MAX_TARGET_SIZE,
    max_indel_size: int = MAX_INDEL_SIZE,
):
    """Sorted, merged, deduped target list."""
    b = ds.batch.to_numpy()
    events = extract_indel_event_arrays(b, max_indel_size)
    return merge_events(events, ds.seq_dict.names, max_target_size)


def resolve_tuning(
    max_indel_size=None, max_consensus_number=None,
    lod_threshold=None, max_target_size=None,
) -> tuple[int, int, float, int]:
    """None-coalesce the realignment tuning knobs against the module
    defaults (shared by the streamed/sharded/monolithic drivers)."""
    return (
        MAX_INDEL_SIZE if max_indel_size is None else max_indel_size,
        MAX_CONSENSUS_NUMBER if max_consensus_number is None
        else max_consensus_number,
        LOD_THRESHOLD if lod_threshold is None else lod_threshold,
        MAX_TARGET_SIZE if max_target_size is None else max_target_size,
    )


def merge_events(
    events,
    names: list[str],
    max_target_size: int = MAX_TARGET_SIZE,
):
    """Sort + overlap-merge + dedupe per-read indel events into targets
    (the global barrier of the streamed/sharded paths: per-window event
    lists concatenate here, so targets spanning window or shard edges
    merge exactly as in the single-batch path).

    ``events`` is either a list of :class:`RealignmentTarget` or the
    hot-path ``[n, 5]`` i64 array from
    :func:`extract_indel_event_arrays`; the merge itself runs over plain
    tuples either way (no per-event object churn)."""
    if isinstance(events, np.ndarray):
        ev = events
    else:
        if not events:
            return []
        ev = np.array(
            [
                [t.contig_idx, t.var_start, t.var_end,
                 t.range_start, t.range_end]
                for t in events
            ],
            np.int64,
        )
    if not len(ev):
        return []
    # sort by (contig NAME, range_start, range_end) — the reference
    # orders by referenceName string, not index; lexsort is stable like
    # Python's sorted
    rank_of = {nm: i for i, nm in enumerate(sorted(names))}
    rank = np.array([rank_of[nm] for nm in names], np.int64)
    order = np.lexsort((ev[:, 4], ev[:, 3], rank[ev[:, 0]]))
    rows = ev[order].tolist()

    merged: list[list] = []  # [contig, vs, ve, rs, re] (vs=-1: none)
    for c, vs, ve, rs, re in rows:
        if merged:
            m = merged[-1]
            m_var = m[1] >= 0
            t_var = vs >= 0
            # TargetOrdering.overlap: either variation overlaps the
            # other's read span
            if m[0] == c and (
                (m_var and m[2] > rs and re > m[1])
                or (t_var and ve > m[3] and m[4] > vs)
            ):
                m[1] = (
                    min(m[1], vs) if m_var and t_var
                    else (m[1] if m_var else vs)
                )
                m[2] = (
                    max(m[2], ve) if m_var and t_var
                    else (m[2] if m_var else ve)
                )
                m[3] = min(m[3], rs)
                m[4] = max(m[4], re)
                continue
            if m[0] == c and m[3] == rs and m[4] == re:
                continue  # TreeSet equality on readRange: duplicate drop
        merged.append([c, vs, ve, rs, re])
    return [
        RealignmentTarget(int(c), int(vs), int(ve), int(rs), int(re))
        for c, vs, ve, rs, re in merged
        if re - rs <= max_target_size
    ]


def map_reads_to_targets(
    read_contig_rank, read_start, read_end, mapped_mask,
    target_rank, target_start, target_end,
) -> np.ndarray:
    """Vectorized replica of RealignIndels.mapToTarget's set-halving
    search (:72-94), including its pruning rule and the
    ``-1 - start/3000`` empty-target spreading."""
    n = len(read_start)
    nt = len(target_start)
    lo = np.zeros(n, dtype=np.int64)
    hi = np.full(n, nt, dtype=np.int64)
    while True:
        size = hi - lo
        if (size <= 1).all():
            break
        mult = size > 1
        mid = lo + size // 2
        m = np.clip(mid, 0, nt - 1)
        # lt(targets[mid], read): target orders before read (name,start,end)
        t_key_lt = (
            (target_rank[m] < read_contig_rank)
            | ((target_rank[m] == read_contig_rank) & (target_start[m] < read_start))
            | ((target_rank[m] == read_contig_rank) & (target_start[m] == read_start)
               & (target_end[m] < read_end))
        ) & mapped_mask
        hi = np.where(mult & t_key_lt, mid, hi)
        lo = np.where(mult & ~t_key_lt, mid, lo)
    t = np.clip(lo, 0, nt - 1)
    contains = (
        mapped_mask
        & (target_rank[t] == read_contig_rank)
        & (target_end[t] > read_start)
        & (read_end > target_start[t])
    )
    # Scala's `/` truncates toward zero, so the reference's unmapped
    # (start = -1) sentinel is -1 - 0 = -1; Python's floor division
    # would give -1 - (-1) = 0, a *valid* target index
    empty = np.where(
        read_start >= 0, -1 - read_start // 3000, -1
    ).astype(np.int64)
    return np.where(contains, t, empty)


def map_reads_to_targets_overlap(
    read_contig_rank, read_start, read_end, mapped_mask,
    target_rank, target_start, target_end,
) -> np.ndarray:
    """Interval mapping: each read goes to the *first target whose read
    range it overlaps* (GATK's IntervalListReferenceOrderedData walk).

    The reference's set-halving search (:func:`map_reads_to_targets`)
    keeps the *head* half when the probe orders before the read
    (RealignIndels.scala:87-91), so with more than one target most
    overlapping reads land on a non-overlapping probe and fall out of
    realignment entirely; its own suite only exercises single-target
    sets (RealignIndelsSuite.scala:54-55).  This mode restores the
    stated semantics; ``map_reads_to_targets`` remains for bit-parity.

    Vectorized: targets sorted by (rank, start); with a composite
    coordinate and a running max of target ends, the first overlapping
    target is one searchsorted (cummax is monotone) + one bounds check.
    """
    nt = len(target_start)
    n = len(read_start)
    if nt == 0:
        return np.where(
            read_start >= 0, -1 - read_start // 3000, -1
        ).astype(np.int64)
    BIG = np.int64(1) << 40
    t_s = target_rank * BIG + target_start
    t_e = target_rank * BIG + target_end
    order = np.argsort(t_s, kind="stable")
    t_s, t_e = t_s[order], t_e[order]
    cummax_e = np.maximum.accumulate(t_e)
    r_s = read_contig_rank * BIG + read_start
    r_e = read_contig_rank * BIG + read_end
    j = np.searchsorted(cummax_e, r_s, side="right")
    jc = np.clip(j, 0, nt - 1)
    contains = (
        mapped_mask & (j < nt) & (t_s[jc] < r_e) & (t_e[jc] > r_s)
    )
    # Scala's `/` truncates toward zero, so the reference's unmapped
    # (start = -1) sentinel is -1 - 0 = -1; Python's floor division
    # would give -1 - (-1) = 0, a *valid* target index
    empty = np.where(
        read_start >= 0, -1 - read_start // 3000, -1
    ).astype(np.int64)
    return np.where(contains, order[jc], empty)


def map_batch_to_targets(b, targets, names, mode: str = "overlap") -> np.ndarray:
    """Target index per row of a batch (-k spreading for unmatched rows).
    The candidate filter of the streamed/sharded paths: rows with
    tidx >= 0 are gathered for realignment, everything else passes
    through untouched.

    ``mode="overlap"`` (default) maps every read to the first target it
    overlaps; ``mode="faithful"`` replicates the reference's set-halving
    search bit-for-bit, quirks included (see
    :func:`map_reads_to_targets_overlap` for why they differ).
    """
    if not targets:
        return np.full(b.n_rows, -1, dtype=np.int64)
    rank_of_name = {nm: i for i, nm in enumerate(sorted(names))}
    contig_rank = np.array([rank_of_name[nm] for nm in names], dtype=np.int64)
    t_rank = np.array(
        [contig_rank[t.contig_idx] for t in targets], dtype=np.int64
    )
    t_start = np.array([t.range_start for t in targets], dtype=np.int64)
    t_end = np.array([t.range_end for t in targets], dtype=np.int64)
    flags = np.asarray(b.flags)
    mapped = ((flags & schema.FLAG_UNMAPPED) == 0) & np.asarray(b.valid)
    read_rank = np.where(
        mapped,
        contig_rank[np.clip(np.asarray(b.contig_idx), 0, len(names) - 1)],
        -1,
    )
    fn = (
        map_reads_to_targets_overlap
        if mode == "overlap"
        else map_reads_to_targets
    )
    return fn(
        read_rank, np.asarray(b.start).astype(np.int64),
        np.asarray(b.end).astype(np.int64), mapped, t_rank, t_start, t_end,
    )


# --------------------------------------------------------------------------
# Batched sweep kernel (device)
# --------------------------------------------------------------------------
def _pow2(n: int, minimum: int) -> int:
    return max(minimum, 1 << (max(int(n), 1) - 1).bit_length())


def sweep_bucket_shape(read_len: int, cons_len: int) -> tuple[int, int]:
    """Padded (lr, lc) bucket for one (read, consensus) sweep task.

    The kernel yields ``lc - lr + 1`` offsets but the reference sweeps
    offsets ``o < cons_len - read_len``; when ``lr`` rounds up past
    ``read_len`` the consensus bucket must absorb the padding
    (``lc >= cons_len + lr - read_len``) or tail offsets are silently
    lost (e.g. read_len=100 -> lr=128 with cons_len=250 needs lc=512,
    not 256, to represent offsets 129..149)."""
    lr = _pow2(read_len, 32)
    lc = _pow2(max(cons_len + (lr - read_len), lr + 1), 64)
    return lr, lc


@partial(jax.jit, static_argnames=("off", "rt", "lr"))
def sweep_gemm_kernel(read_codes, read_quals, read_len, read_mask,
                      cons, cons_len, off: int, rt: int, lr: int):
    """MXU-shaped sweep: batched GEMM over (target, consensus) pairs.

    Same math as :func:`sweep_kernel` — mismatchQual(b, o) = totalQual -
    one-hot match correlation, offsets ``o < cons_len - read_len`` — but
    laid out as ``[P, rt, lr*6] x [P, lr*6, off]`` batched matmuls so the
    contraction runs on the MXU instead of a degenerate 6-channel conv
    (measured ~9 GFLOP/s on the conv formulation vs matmul peak).  All
    values are integers: bf16 inputs are exact (quals <= 93 need 7
    mantissa bits), the MXU accumulates in f32 (exact to 2^24), so
    results are bit-identical to the f32 conv path.

    Pair slot ``p`` sweeps reads ``read_codes[p*rt:(p+1)*rt]`` against
    ``cons[p]`` (``lc = off + lr``); every compiled shape depends only on
    the static ``(off, rt, lr)`` tier, never on dataset size.  Padded
    read slots have ``read_mask`` False; padded pairs have ``cons_len``
    0.  Returns (best_q f32[P, rt], best_o i32[P, rt])."""
    P = cons.shape[0]
    rc = read_codes.reshape(P, rt, lr)
    rl = read_len.reshape(P, rt)
    pos = jnp.arange(lr)
    qf = jnp.where(
        (pos[None, None, :] < rl[..., None])
        & read_mask.reshape(P, rt)[..., None],
        read_quals.reshape(P, rt, lr), 0,
    ).astype(jnp.int32)
    A = (
        jax.nn.one_hot(rc, 6, dtype=jnp.bfloat16)
        * qf[..., None].astype(jnp.bfloat16)
    ).reshape(P, rt, lr * 6)
    oh = jax.nn.one_hot(cons, 6, dtype=jnp.bfloat16)       # [P, lc, 6]
    idx = jnp.arange(lr)[:, None] + jnp.arange(off)[None, :]
    B = oh[:, idx, :]                                      # [P, lr, off, 6]
    B = B.transpose(0, 1, 3, 2).reshape(P, lr * 6, off)
    match = jnp.einsum(
        "prk,pko->pro", A, B, preferred_element_type=jnp.float32
    )
    total_q = qf.sum(-1)[..., None].astype(jnp.float32)    # [P, rt, 1]
    mismatch = total_q - match
    valid = (
        jnp.arange(off)[None, None, :]
        < (cons_len[:, None] - rl)[..., None]
    )
    masked = jnp.where(valid, mismatch, jnp.inf)
    best_o = jnp.argmin(masked, -1).astype(jnp.int32)
    best_q = masked.min(-1)
    has = valid.any(-1)
    return jnp.where(has, best_q, jnp.inf), jnp.where(has, best_o, -1)


# pair-batch size per (off, rt) tier: bounds the im2col temporary
# [P, lr, off, 6] bf16 while keeping ~4k tasks per dispatch
def _sweep_gemm_P(off: int, rt: int) -> int:
    base = max(8, (1 << 17) // off)  # 256 at off=512, halving upward
    return max(2, base // (rt // 16)) if rt > 16 else base


@partial(jax.jit, static_argnames=("lr", "lc"))
def sweep_kernel_gather(read_codes, read_quals, read_len, cons_tbl,
                        clen_tbl, cons_idx, lr: int, lc: int):
    """Sweep with a deduplicated consensus table.

    A chunk's tasks reference each consensus once per read in its group,
    so shipping the [CH, lc] consensus rows per-task re-sends every byte
    group-size times over the host->device link.  Instead the
    unique consensus rows travel once and the per-task rows are gathered
    ON DEVICE from the table.
    """
    return sweep_kernel(
        read_codes, read_quals, read_len,
        cons_tbl[cons_idx], clen_tbl[cons_idx], lr, lc,
    )


@partial(jax.jit, static_argnames=("lr", "lc"))
def sweep_kernel(read_codes, read_quals, read_len, cons_codes, cons_len,
                 lr: int, lc: int):
    """For each (read, consensus) pair: mismatch quality at every offset.

    mismatchQual(b, o) = sum_i q_i [read_i != cons_{o+i}]
                       = totalQual(b) - sum_i q_i [read_i == cons_{o+i}]
    with the match-correlation computed as a one-hot conv per pair.
    Valid offsets o in [0, cons_len - read_len) (the reference's
    exclusive sweep loop).  Returns (best_qual i32[B], best_offset i32[B])
    with the smallest offset winning ties; best_offset = -1 when no valid
    offset exists.
    """
    B = read_codes.shape[0]
    in_read = jnp.arange(lr)[None, :] < read_len[:, None]
    q = jnp.where(in_read, read_quals, 0).astype(jnp.float32)
    total_q = q.sum(axis=1)
    # one-hot over the 6 codes (N==N matches, PAD never matches quals=0)
    read_oh = jax.nn.one_hot(read_codes, 6, dtype=jnp.float32) * q[..., None]
    cons_oh = jax.nn.one_hot(cons_codes, 6, dtype=jnp.float32)

    def corr(x, w):
        # x: [lc, 6] one-hot consensus; w: [lr, 6] qual-weighted read
        return jax.lax.conv_general_dilated(
            x[None], w[:, :, None],
            window_strides=(1,), padding="VALID",
            dimension_numbers=("NWC", "WIO", "NWC"),
        )[0, :, 0]

    match = jax.vmap(corr)(cons_oh, read_oh)  # [B, lc - lr + 1]
    mismatch = total_q[:, None] - match
    n_off = lc - lr + 1
    offs = jnp.arange(n_off)[None, :]
    valid = offs < (cons_len - read_len)[:, None]  # exclusive upper bound
    masked = jnp.where(valid, mismatch, jnp.inf)
    best_off = jnp.argmin(masked, axis=1).astype(jnp.int32)
    best_q = jnp.min(masked, axis=1)
    has_any = valid.any(axis=1)
    return (
        jnp.where(has_any, best_q, jnp.inf),
        jnp.where(has_any, best_off, -1),
    )


def _group_candidates(b, tidx, mapped):
    """Candidate rows grouped by target, position-sorted within a group
    (the reference sorts the RDD before target mapping).

    Returns ``(srows, goff, gtid)``: flat row indices, group offsets
    (``goff[g]:goff[g+1]`` slices ``srows``), and the target id per
    group.  Shared by the Python and native paths — group iteration
    order drives the rng.sample call sequence, so both paths MUST use
    this exact construction for bit-identical output."""
    sel = np.flatnonzero(mapped & (tidx >= 0))
    if not len(sel):
        z = np.zeros(0, np.int64)
        return z, np.zeros(1, np.int64), z
    order = np.lexsort(
        (sel, np.asarray(b.start)[sel].astype(np.int64), tidx[sel])
    )
    srows = sel[order]
    stid = tidx[srows]
    bounds = np.flatnonzero(np.diff(stid) != 0) + 1
    goff = np.concatenate(
        [np.zeros(1, np.int64), bounds.astype(np.int64),
         np.array([len(srows)], np.int64)]
    )
    gtid = stid[goff[:-1]].astype(np.int64)
    return srows, goff, gtid


def _sum_mismatch_quality(seq: str, ref: str, quals) -> int:
    """sumMismatchQualityIgnoreCigar: positional zip, truncating to the
    shorter string (RealignIndels.scala:429-440) — vectorized byte
    compare instead of a per-char generator."""
    n = min(len(seq), len(ref), len(quals))
    if n == 0:
        return 0
    a = np.frombuffer(seq.encode("ascii"), np.uint8, n)
    b = np.frombuffer(ref.encode("ascii"), np.uint8, n)
    q = np.asarray(quals[:n], np.int64)
    return int(q[a != b].sum())


# --------------------------------------------------------------------------
# Per-target realignment (host orchestration)
# --------------------------------------------------------------------------
@dataclass
class _Read:
    """Host view of one read under realignment.

    ``md`` is parsed lazily — only reads whose CIGAR is not a single M
    run need it (left-alignment, reference slices through indels); for
    the pure-M majority the precomputed ``ref`` string (from the
    vectorized MD tokenizer) and per-row mismatch-qual sums replace all
    per-read MD work.  ``dirty`` marks reads whose alignment changed in
    preprocessing (left-align / SW), which must be written back even
    when the consensus pass leaves them alone.
    """

    row: int
    seq: str
    quals: np.ndarray
    start: int
    cigar: list  # [(len, op)]
    md: Optional[MdTag]
    mapq: int
    ref: Optional[str] = None  # implied reference over the aligned span
    pure: bool = False  # single-M CIGAR
    dirty: bool = False
    codes: Optional[np.ndarray] = None  # base codes (sweep input, cached)

    @property
    def end(self) -> int:
        return self.start + cigar_ref_len(self.cigar)


def _get_reference_from_reads(reads: list[_Read], extra_refs=()):
    """RealignIndels.getReferenceFromReads (:185-215).

    ``extra_refs`` carries (ref, start, end) tuples for reads that exist
    only as columnar rows (the pure clean majority never materialized as
    ``_Read`` objects); they splice into the window exactly as reads do.
    """
    refs = list(extra_refs)
    for r in reads:
        ref = r.ref
        if ref is None and r.md is not None:  # directly-built _Reads
            ref = r.md.get_reference(r.seq, cigar_to_string(r.cigar))
        if ref is not None:
            refs.append((ref, r.start, r.end))
    if not refs:
        raise ValueError("no reads with MD tags in target group")
    refs.sort(key=lambda x: x[1])
    ref, cur = "", refs[0][1]
    ref_start = refs[0][1]
    for s, start, end in refs:
        if end < cur:
            continue
        if cur >= start:
            ref += s[cur - start :]
            cur = end
        else:
            raise ValueError(f"gap at {cur} with {start},{end} rebuilding reference")
    return ref, ref_start, cur


@dataclass(frozen=True)
class Consensus:
    """models/Consensus.scala: an alternate allele to splice into the
    reference — insertion when index spans 1bp."""

    consensus: str
    contig_idx: int
    index_start: int
    index_end: int

    def insert_into_reference(self, reference: str, ref_start: int, ref_end: int) -> str:
        if (self.index_start < ref_start or self.index_start > ref_end
                or self.index_end - 1 < ref_start or self.index_end - 1 > ref_end):
            raise ValueError("consensus and reference do not overlap")
        return (
            reference[: self.index_start - ref_start]
            + self.consensus
            + reference[self.index_end - 1 - ref_start :]
        )


def generate_alternate_consensus(seq: str, start: int, contig_idx: int,
                                 cigar: list) -> Optional[Consensus]:
    """Consensus.generateAlternateConsensus (:25-52)."""
    read_pos = 0
    ref_pos = start
    if sum(1 for _, op in cigar if op in "ID") != 1:
        return None
    for n, op in cigar:
        if op == "I":
            return Consensus(seq[read_pos : read_pos + n], contig_idx,
                             ref_pos, ref_pos + 1)
        if op == "D":
            return Consensus("", contig_idx, ref_pos, ref_pos + n + 1)
        if op in "M=X":
            read_pos += n
            ref_pos += n
        else:
            return None
    return None


def realign_indels(
    ds: AlignmentDataset,
    consensus_model: str = "reads",
    known_indels: Optional[IndelTable] = None,
    max_indel_size: int = MAX_INDEL_SIZE,
    max_consensus_number: int = MAX_CONSENSUS_NUMBER,
    lod_threshold: float = LOD_THRESHOLD,
    max_target_size: int = MAX_TARGET_SIZE,
    sw_weights: tuple = (1.0, -0.333, -0.5, -0.5),
    rng: Optional[random.Random] = None,
    target_mapping: str = "overlap",
    overlap_work=None,
    sweep_devices=None,
) -> AlignmentDataset:
    """GATK-style local realignment (RealignIndels.scala:235-387).

    Dispatches to the native-prep path (C++ per-read string walks +
    vectorized sweep dispatch, native/realign.cpp) when available; the
    pure-Python implementation below remains the semantic oracle (the
    two are differentially tested) and the fallback for the
    ``smithwaterman`` consensus model and native-less installs.

    ``overlap_work``: optional zero-arg callable invoked after the
    device sweeps are dispatched and before their results are fetched —
    host work placed there (e.g. the streamed pipeline's BQSR
    observation pass) runs under the device's queue-drain window.
    Runs exactly once whichever implementation serves the call (a
    once-guard here covers the native path handing off to the fallback
    AFTER it already ran the work).  Whether the work actually ran inside
    the native sweep-dispatch window (i.e. genuinely hidden under the
    device queue drain) is reported back on the callable itself as
    ``overlap_ran_in_dispatch`` — the streamed pipeline's stage table
    only credits the overlap when it really happened (on the Python
    fallback and the no-target early-outs the work runs serially).

    ``sweep_devices``: explicit device set to fan the sweep GEMM
    buckets across (the streamed pipeline passes its pool/mesh device
    set) — chunks place round-robin weighted by
    :class:`~adam_tpu.parallel.device_pool.SweepSchedule` (per-device
    probe TFLOP/s pacing), instead of all landing on the default
    device.  Placement never changes the sweep values, so the output
    is bit-identical regardless of fan-out."""
    if overlap_work is not None:
        _orig_overlap = overlap_work
        _overlap_state = {"done": False}

        def overlap_work(in_dispatch: bool = False):
            if not _overlap_state["done"]:
                _overlap_state["done"] = True
                try:
                    _orig_overlap.overlap_ran_in_dispatch = bool(in_dispatch)
                except (AttributeError, TypeError):
                    pass  # exotic callable: accounting stays pessimistic
                _orig_overlap()

        overlap_work._accepts_in_dispatch = True

    if consensus_model != "smithwaterman" and os.environ.get(
        "ADAM_TPU_REALIGN", ""
    ) != "py":
        out = _realign_indels_native(
            ds, consensus_model, known_indels, max_indel_size,
            max_consensus_number, lod_threshold, max_target_size, rng,
            target_mapping, overlap_work=overlap_work,
            sweep_devices=sweep_devices,
        )
        if out is not None:
            return out
    if overlap_work is not None:
        overlap_work()  # no async device phase on the fallback path
    return _realign_indels_py(
        ds, consensus_model, known_indels, max_indel_size,
        max_consensus_number, lod_threshold, max_target_size, sw_weights,
        rng, target_mapping, sweep_devices=sweep_devices,
    )


def _realign_indels_py(
    ds: AlignmentDataset,
    consensus_model: str = "reads",
    known_indels: Optional[IndelTable] = None,
    max_indel_size: int = MAX_INDEL_SIZE,
    max_consensus_number: int = MAX_CONSENSUS_NUMBER,
    lod_threshold: float = LOD_THRESHOLD,
    max_target_size: int = MAX_TARGET_SIZE,
    sw_weights: tuple = (1.0, -0.333, -0.5, -0.5),
    rng: Optional[random.Random] = None,
    target_mapping: str = "overlap",
    sweep_devices=None,
) -> AlignmentDataset:
    with _tele.TRACE.span(_tele.SPAN_REALIGN_TARGETS):
        b = ds.batch.to_numpy()
        n = b.n_rows
        if n == 0:
            return ds
        targets = find_targets(ds, max_target_size, max_indel_size)
        if not targets:
            return ds
        names = ds.seq_dict.names
        flags = np.asarray(b.flags)
        mapped = ((flags & schema.FLAG_UNMAPPED) == 0) & np.asarray(b.valid)
        tidx = map_batch_to_targets(b, targets, names, mode=target_mapping)

        # group rows by target, position-sorted within the group — the
        # shared vectorized construction (see _group_candidates for why)
        srows, goff, gtid = _group_candidates(b, tidx, mapped)
        groups: dict[int, list[int]] = {
            int(gtid[g]): [int(i) for i in srows[goff[g]:goff[g + 1]]]
            for g in range(len(gtid))
        }

    with _tele.TRACE.span(_tele.SPAN_REALIGN_CONSENSUS):
        new_batch = jax.tree.map(np.array, b)  # writable copies
        side = ds.sidecar

        # vectorized per-row MD columns (one native tokenize, no per-read
        # parse): mismatch mask -> to_clean membership + positional
        # orig-qual sums; ref codes -> implied reference for every
        # single-M read
        from adam_tpu.ops.mdtag import batch_md_arrays

        is_mm, ref_codes, has_md_vec = batch_md_arrays(
            ds.batch, side, need_ref_codes=True
        )
        row_has_mm = is_mm.any(axis=1)
        mm_qual = np.where(is_mm, np.asarray(b.quals), 0).sum(axis=1)
        # sparse overrides: only realigned rows get new MD/attrs — the
        # full sidecar is never materialized as python strings (8M reads
        # would cost ~30s just in string churn)
        new_md: dict[int, Optional[str]] = {}
        new_attrs: dict[int, str] = {}
        rng = rng or random.Random(0)

        # ---- phase 1 (host): per group, rebuild reference + consensuses
        # bulk per-row precomputation over all grouped rows (one
        # LUT/decode pass instead of a numpy-call per read — the
        # single-core host is the pipeline's scarce resource)
        all_rows = (
            np.concatenate([np.asarray(r) for r in groups.values()])
            if groups else np.zeros(0, np.int64)
        )
        seq_of: dict[int, str] = {}
        ref_of: dict[int, str] = {}
        if len(all_rows):
            purev = (
                (np.asarray(b.cigar_n)[all_rows] == 1)
                & (np.asarray(b.cigar_ops)[all_rows, 0] == schema.CIGAR_M)
                & has_md_vec[all_rows]
            )
            prows = all_rows[purev]
            if len(prows):
                ref_of = dict(
                    zip(
                        (int(i) for i in prows),
                        schema.decode_bases_bulk(
                            ref_codes[prows], np.asarray(b.lengths)[prows]
                        ),
                    )
                )
            # sequences are only needed for rows that materialize a _Read —
            # the pure clean majority (in ref_of, no mismatches) is skipped
            # by the light path below and never decodes
            heavy = all_rows[~(purev & ~row_has_mm[all_rows])]
            if len(heavy):
                seq_of = dict(
                    zip(
                        (int(i) for i in heavy),
                        schema.decode_bases_bulk(
                            np.asarray(b.bases)[heavy],
                            np.asarray(b.lengths)[heavy],
                        ),
                    )
                )
        _CC = schema.CIGAR_CHARS

        group_ctx = {}
        res_q: dict[int, np.ndarray] = {}  # per target: [n_reads, n_cons]
        res_o: dict[int, np.ndarray] = {}

        # ---- phase 2 machinery, interleaved with phase 1 ------------------
        # tasks are grouped into power-of-two (read, consensus) length
        # buckets so a single max_target_size consensus doesn't inflate
        # every (read x consensus) pair in the batch, and each bucket
        # flushes to the device in FIXED-size chunks (one compiled shape
        # per (lr, lc) bucket — a data-dependent batch dim compiled a
        # fresh kernel per size).  Chunks dispatch asynchronously *while
        # phase 1 is still building later groups* (quals travel as u8;
        # the kernel widens on device); results stay on device and one
        # fetch pass drains them after the last flush — the chip sweeps
        # target k's pairs while the single-core host rebuilds target
        # k+1's reference.
        CH = 8192   # tasks per dispatch (fixed -> one compiled shape/bucket)
        # consensus slots: large enough that dense data (tasks-per-consensus
        # = group size >= 4) never flushes early on the cons trigger, small
        # enough that the always-full-size table transfer stays ~1 MB
        NC = 2048
        _buckets: dict[tuple[int, int], dict] = {}
        _pending = []  # (chunk tasks, device (best_q, best_o))
        _remaining: dict[int, int] = {}  # target -> sweep results outstanding
        # fan sweep chunks across the pool/mesh device set (probe-paced
        # weighted round-robin); None = the default device, the old behavior
        _sched = None
        if sweep_devices is not None and len(sweep_devices) > 1:
            from adam_tpu.parallel.device_pool import SweepSchedule

            _sched = SweepSchedule(sweep_devices)

        def _flush_bucket(key) -> None:
            lr, lc = key
            st = _buckets.pop(key)
            tasks = st["tasks"]
            # two shape tiers per bucket: small flushes (residuals, small
            # inputs) use a 1024-task shape so a near-empty chunk doesn't
            # pay the full 8192-row compute on slow backends; both tiers
            # stay fixed so the compile-shape set is bounded at two
            ch = CH if len(tasks) > 1024 else 1024
            nc = NC if ch == CH else 1024
            rc = np.full((ch, lr), schema.BASE_PAD, np.uint8)
            rq = np.zeros((ch, lr), np.uint8)
            rl = np.zeros(ch, np.int32)
            ct = np.full((nc, lc), schema.BASE_PAD, np.uint8)
            cl = np.zeros(nc, np.int32)
            for s, codes in enumerate(st["cons"]):
                ct[s, : len(codes)] = codes
                cl[s] = len(codes)
            cidx = np.zeros(ch, np.int32)
            for k, (_t, _ri, _ci, r, cs) in enumerate(tasks):
                rc[k, : len(r.codes)] = r.codes
                rq[k, : len(r.quals)] = r.quals
                rl[k] = len(r.codes)
                cidx[k] = cs
            # padded task rows gather consensus slot 0 and are never read back
            from adam_tpu.parallel.device_pool import putter as _putter
            from adam_tpu.utils import compile_ledger

            dev = _sched.next_device() if _sched is not None else None
            _put = _putter(dev)  # commit + h2d transfer accounting
            with compile_ledger.track(("realign.sweep", ch, lr, nc, lc), dev):
                _pending.append((tasks, sweep_kernel_gather(
                    _put(rc), _put(rq), _put(rl),
                    _put(ct), _put(cl), _put(cidx), lr, lc,
                )))

        def _enqueue_sweep(task) -> None:
            t, ri, ci, r, cons_codes = task
            key = sweep_bucket_shape(len(r.codes), len(cons_codes))
            st = _buckets.get(key)
            if st is None:
                st = _buckets[key] = {"tasks": [], "cmap": {}, "cons": []}
            cs = st["cmap"].get(id(cons_codes))
            if cs is None:
                cs = len(st["cons"])
                st["cmap"][id(cons_codes)] = cs
                st["cons"].append(cons_codes)
            st["tasks"].append((t, ri, ci, r, cs))
            if len(st["tasks"]) >= CH or len(st["cons"]) >= NC:
                _flush_bucket(key)
        for t, rows in groups.items():
            reads = []
            extra_refs = []
            for i in rows:
                if i in ref_of and not row_has_mm[i]:
                    # pure clean majority: never swept, never rewritten —
                    # contributes only its reference slice to the window
                    # rebuild, so no _Read is materialized at all
                    s0 = int(b.start[i])
                    extra_refs.append((ref_of[i], s0, s0 + int(b.lengths[i])))
                    continue
                L = int(b.lengths[i])
                seq = seq_of[i]
                nc = int(b.cigar_n[i])
                cig = [
                    (int(b.cigar_lens[i, k]), _CC[b.cigar_ops[i, k]])
                    for k in range(nc)
                ]
                pure = nc == 1 and b.cigar_ops[i, 0] == schema.CIGAR_M
                has_md_i = bool(has_md_vec[i])
                if pure or not has_md_i:
                    md = None  # pure-M rows never need a parsed MdTag
                else:
                    md = MdTag.parse(side.md[i], int(b.start[i]))
                if not has_md_i:
                    ref = None
                elif pure:
                    ref = ref_of[i]
                else:
                    ref = md.get_reference(seq, cig)
                reads.append(
                    _Read(
                        row=i,
                        seq=seq,
                        quals=np.asarray(b.quals[i][:L], np.int32),
                        start=int(b.start[i]),
                        cigar=cig,
                        md=md,
                        mapq=int(b.mapq[i]),
                        ref=ref,
                        pure=pure,
                        codes=np.asarray(b.bases[i][:L]),
                    )
                )
            # reads that already match the reference pass through untouched
            to_clean = [
                r for r in reads if not has_md_vec[r.row] or row_has_mm[r.row]
            ]
            if not to_clean:
                continue
            try:
                reference, ref_start, ref_end = _get_reference_from_reads(
                    reads, extra_refs
                )
            except ValueError:
                continue
            contig_idx = targets[t].contig_idx

            # preprocess: left-normalize single-indel reads (and SW-realign
            # everything first under the smithwaterman model)
            if consensus_model == "smithwaterman":
                to_clean = _sw_preprocess(
                    to_clean, reference, ref_start, sw_weights
                )
            processed = []
            for r in to_clean:
                if cigar_num_alignment_blocks(r.cigar) == 2:
                    new_cigar = left_align_indel(r.seq, r.cigar, r.md)
                    if new_cigar != r.cigar:
                        md = MdTag.move_alignment(
                            r.ref, r.seq, cigar_to_string(new_cigar), r.start,
                        ) if r.md is not None else None
                        processed.append(
                            dc_replace(r, cigar=new_cigar, md=md, dirty=True)
                        )
                    else:
                        processed.append(r)
                else:
                    processed.append(r)
            to_clean = processed

            # find consensuses
            consensuses: list[Consensus] = []
            if consensus_model == "knowns" and known_indels is not None:
                region_name = names[contig_idx]
                from adam_tpu.models.positions import ReferenceRegion

                for rec in known_indels.get_indels_in_region(
                    ReferenceRegion(region_name, ref_start, ref_end)
                ):
                    consensuses.append(
                        Consensus(rec.consensus, contig_idx,
                                  rec.region.start, rec.region.end)
                    )
            else:
                for r in to_clean:
                    if r.md is None:
                        continue
                    c = generate_alternate_consensus(
                        r.seq, r.start, contig_idx, r.cigar
                    )
                    if c is not None:
                        consensuses.append(c)
            # distinct
            seen = set()
            uniq = []
            for c in consensuses:
                key = (c.consensus, c.index_start, c.index_end)
                if key not in seen:
                    seen.add(key)
                    uniq.append(c)
            consensuses = uniq
            if len(consensuses) > max_consensus_number:
                consensuses = rng.sample(consensuses, max_consensus_number)
            if not consensuses:
                # still keep preprocessing results (readsToClean ++ realigned)
                _write_back(
                    new_batch, side, new_md, new_attrs, to_clean,
                    realigned={},
                )
                continue

            group_ctx[t] = (
                to_clean, consensuses, reference, ref_start, ref_end
            )
            res_q[t] = np.full(
                (len(to_clean), len(consensuses)), np.inf, np.float32
            )
            res_o[t] = np.full((len(to_clean), len(consensuses)), -1, np.int32)
            _remaining[t] = len(to_clean) * len(consensuses)
            for ci, c in enumerate(consensuses):
                cons_seq = c.insert_into_reference(
                    reference, ref_start, ref_end
                )
                # once per consensus
                cons_codes = schema.encode_bases(cons_seq)
                for ri, r in enumerate(to_clean):
                    _enqueue_sweep((t, ri, ci, r, cons_codes))

        del seq_of, ref_of  # decoded strings live only through phase 1

    # ---- phase 2 drain + phase 3, interleaved ----
    # flush residual chunks, then finish each target the moment its last
    # sweep result lands — the host rewrites completed targets (phase 3)
    # while the device is still computing later chunks, instead of
    # blocking through the whole fetch tail first.  Targets write to
    # disjoint rows, so completion order doesn't affect the output.
    with _tele.TRACE.span(_tele.SPAN_REALIGN_SWEEP_DISPATCH):
        for key in list(_buckets):
            if _buckets[key]["tasks"]:
                _flush_bucket(key)

    def _finish_target(t: int) -> None:
        to_clean, consensuses, reference, ref_start, ref_end = group_ctx[t]

        def _orig_qual(r):
            if r.dirty and r.md is not None:
                return _sum_mismatch_quality(
                    r.seq,
                    r.md.get_reference(r.seq, cigar_to_string(r.cigar)),
                    r.quals,
                )
            if r.pure:  # positional mismatch-qual sum, precomputed
                return int(mm_qual[r.row])
            return _sum_mismatch_quality(r.seq, r.ref or "", r.quals)

        orig_quals = [_orig_qual(r) for r in to_clean]
        pre_total = sum(orig_quals)
        # vectorized consensus scoring over the [n_reads, n_cons] sweep
        # result arrays: per cell take min(sweep, orig) (sweep value
        # truncated to int, as the reference's Int sum does), column
        # totals, best = min with the LATER consensus winning ties
        # (the reference's list-prepend + left fold)
        q = res_q[t]
        o = res_o[t]
        orig = np.asarray(orig_quals, np.int64)
        use = q < orig[:, None]
        qi = np.zeros_like(q, dtype=np.int64)
        qi[use] = q[use].astype(np.int64)
        contrib = np.where(use, qi, orig[:, None])
        totals = contrib.sum(axis=0)
        nc = len(consensuses)
        best_ci = int(nc - 1 - np.argmin(totals[::-1]))
        best_total = int(totals[best_ci])
        best_map = np.where(use[:, best_ci], o[:, best_ci], -1)
        lod = (pre_total - best_total) / 10.0
        # per-target decision logs, the RealignIndels.scala:317-379 trail
        _log = logging.getLogger(__name__)
        _log.debug(
            "On target %d [%d, %d), before realignment, sum was %d; "
            "best consensus %d has sum %d (LOD %.2f)",
            t, ref_start, ref_start + len(reference), pre_total,
            best_ci, best_total, lod,
        )
        realigned = {}
        if lod > lod_threshold:
            cons = consensuses[best_ci]
            for ri, r in enumerate(to_clean):
                o = best_map[ri]
                if o == -1:
                    continue
                new_start = ref_start + o
                if cons.index_start == cons.index_end - 1:  # insertion
                    id_elem = (len(cons.consensus), "I")
                    end_len = len(r.seq) - len(cons.consensus) - (cons.index_start - new_start)
                    end_penalty = -len(cons.consensus)
                else:  # deletion
                    id_elem = (cons.index_end - 1 - cons.index_start, "D")
                    end_len = len(r.seq) - (cons.index_start - new_start)
                    end_penalty = len(cons.consensus)
                head_len = cons.index_start - new_start
                if head_len > 0 and end_len > 0:
                    new_cigar = [(head_len, "M"), id_elem, (end_len, "M")]
                    new_end = new_start + len(r.seq) + end_penalty
                else:
                    # the swept position doesn't span the consensus indel
                    # (read entirely before/after it): a plain gapless
                    # alignment at the new offset.  The reference emits a
                    # negative-length M here (RealignIndels.scala:344-360,
                    # never hit by its single-target suite) — an invalid
                    # CIGAR we decline to reproduce.
                    new_cigar = [(len(r.seq), "M")]
                    new_end = new_start + len(r.seq)
                # a swept offset near the region edge can consume more
                # reference than the rebuilt window holds (insertion
                # consensuses are longer than the reference, so valid
                # consensus offsets can overrun it — another walk the
                # reference leaves unguarded): leave the read unrealigned
                if o + (new_end - new_start) > len(reference):
                    continue
                md = MdTag.move_alignment(
                    reference[o:], r.seq, cigar_to_string(new_cigar), new_start
                )
                realigned[ri] = dc_replace(
                    r, start=new_start, cigar=new_cigar, md=md, mapq=r.mapq + 10
                ), new_end
        _write_back(new_batch, side, new_md, new_attrs, to_clean, realigned)

    from adam_tpu.utils.transfer import device_fetch as _dfetch

    # decisions interleave with the fetch: each target is decided as
    # its last sweep result lands, inside realign.sweep_fetch
    with _tele.TRACE.span(_tele.SPAN_REALIGN_SWEEP_FETCH):
        for chunk, out in _pending:
            # drain through the transfer helper so the d2h ledger sees the
            # sweep results (tiny [CH] i32 pairs, but per-pass byte
            # attribution must not have dark spots)
            best_q, best_o = _dfetch(out[0]), _dfetch(out[1])
            for k, (t, ri, ci, _, _) in enumerate(chunk):
                res_q[t][ri, ci] = best_q[k]
                res_o[t][ri, ci] = best_o[k]
                _remaining[t] -= 1
                if _remaining[t] == 0:
                    _finish_target(t)

    with _tele.TRACE.span(_tele.SPAN_REALIGN_DECIDE):
        from adam_tpu.formats.strings import StringColumn, with_overrides

        new_side = dc_replace(
            side,
            md=with_overrides(StringColumn.of(side.md), new_md),
            attrs=with_overrides(StringColumn.of(side.attrs), new_attrs),
        )
    return ds.with_batch(new_batch, new_side)


def _sw_preprocess(reads, reference, ref_start, weights):
    """ConsensusGeneratorFromSmithWaterman.preprocessReadsForRealignment
    (:40-70): SW-align each read against the region; accept when <= 2
    alignment blocks, rewriting start/cigar/MD (start from the
    reference's own xStart+regionStart rule)."""
    from adam_tpu.ops.smith_waterman import smith_waterman

    out = []
    w_match, w_mismatch, w_insert, w_delete = weights
    for r in reads:
        aln = smith_waterman(r.seq, reference, w_match, w_mismatch,
                             w_insert, w_delete)
        cigar = parse_cigar(aln.cigar_x)
        if cigar_num_alignment_blocks(cigar) <= 2:
            md = MdTag.from_alignment(
                r.seq, reference[aln.x_start :], aln.cigar_x, ref_start
            )
            out.append(
                dc_replace(r, start=aln.x_start + ref_start, cigar=cigar,
                           md=md, dirty=True)
            )
        else:
            out.append(r)
    return out


def _write_back(new_batch, side, new_md, new_attrs, to_clean, realigned):
    """Apply (possibly realigned) host reads back into the batch.

    MD/attr updates land in the sparse ``new_md``/``new_attrs`` override
    dicts (row -> str), merged into the sidecar columns in one pass at
    the end of realign_indels."""
    cmax = new_batch.cmax
    for ri, r in enumerate(to_clean):
        if ri in realigned:
            rr, new_end = realigned[ri]
            old_start = int(new_batch.start[rr.row])
            old_cigar = schema.decode_cigar(
                new_batch.cigar_ops[rr.row], new_batch.cigar_lens[rr.row],
                int(new_batch.cigar_n[rr.row]),
            )
            tag = f"OC:Z:{old_cigar}\tOP:i:{old_start + 1}"
            cur = new_attrs.get(rr.row, side.attrs[rr.row]) or ""
            new_attrs[rr.row] = cur + "\t" + tag if cur else tag
        elif not r.dirty:
            continue  # alignment untouched: nothing to write
        else:
            rr, new_end = r, None
        cig = cigar_to_string(rr.cigar)
        ops, lens, ncig = schema.encode_cigar(cig, max(cmax, len(rr.cigar)))
        if ncig > cmax:
            raise ValueError("realigned cigar exceeds batch cmax")
        new_batch.cigar_ops[rr.row] = ops[:cmax]
        new_batch.cigar_lens[rr.row] = lens[:cmax]
        new_batch.cigar_n[rr.row] = ncig
        new_batch.start[rr.row] = rr.start
        new_batch.mapq[rr.row] = rr.mapq
        if new_end is not None:
            new_batch.end[rr.row] = new_end
        else:
            new_batch.end[rr.row] = rr.end
        if rr.md is not None:
            new_md[rr.row] = rr.md.to_string()


# --------------------------------------------------------------------------
# Native-prep realignment path
# --------------------------------------------------------------------------
def _pow2_vec(n: np.ndarray, minimum: int) -> np.ndarray:
    """Vectorized ``_pow2``: next power of two, floored at ``minimum``."""
    table = np.int64(1) << np.arange(40, dtype=np.int64)
    idx = np.searchsorted(table, np.maximum(np.asarray(n, np.int64), 1))
    return np.maximum(table[idx], minimum)


def _realign_indels_native(
    ds: AlignmentDataset,
    consensus_model: str,
    known_indels: Optional[IndelTable],
    max_indel_size: int,
    max_consensus_number: int,
    lod_threshold: float,
    max_target_size: int,
    rng: Optional[random.Random],
    target_mapping: str,
    overlap_work=None,
    sweep_devices=None,
):
    """Same decisions as :func:`_realign_indels_py`, with the per-read
    host work (MD parse / reference rebuild / left-normalization /
    consensus generation / MD rewrite) in C++ (native/realign.cpp) and
    the sweep task machinery vectorized.  Returns None when the native
    library is unavailable (caller falls back to the Python path)."""
    from adam_tpu import native

    if not native.available():
        return None
    _tr = _tele.TRACE

    def _overlap_once(in_dispatch: bool = False):
        nonlocal overlap_work
        if overlap_work is not None:
            w, overlap_work = overlap_work, None
            if getattr(w, "_accepts_in_dispatch", False):
                w(in_dispatch=in_dispatch)
            else:
                w()

    from adam_tpu.formats.strings import StringColumn, with_overrides

    # phase spans (SweepReadOverReferenceForQuality-style named phases,
    # instrumentation/Timers.scala:25-81); the overlapped work between
    # sweep dispatch and fetch is the caller's own span
    with _tr.span(_tele.SPAN_REALIGN_TARGETS):
        b = ds.batch.to_numpy()
        n = b.n_rows
        targets = (
            find_targets(ds, max_target_size, max_indel_size) if n else []
        )
        srows = ()
        if targets:
            names = ds.seq_dict.names
            flags = np.asarray(b.flags)
            mapped = (
                ((flags & schema.FLAG_UNMAPPED) == 0) & np.asarray(b.valid)
            )
            tidx = map_batch_to_targets(b, targets, names, mode=target_mapping)
            srows, goff, gtid = _group_candidates(b, tidx, mapped)
        if len(srows):
            G = len(goff) - 1
            side = ds.sidecar
            md_col = StringColumn.of(side.md)
            if len(md_col) >= n:
                md_buf, md_off = md_col.buf, md_col.offsets[: n + 1]
                md_valid = md_col.valid[:n] & np.asarray(b.valid)
            else:
                md_buf = np.zeros(0, np.uint8)
                md_off = np.zeros(n + 1, np.int64)
                md_valid = np.zeros(n, bool)
            # consensuses come from the indel table only under the
            # knowns model WITH a table; otherwise (reads model, or
            # knowns without a table) they are generated from the reads,
            # as the Python path's else-branch does (realign.py:994)
            gen_consensus = not (
                consensus_model == "knowns" and known_indels is not None
            )
    if not len(srows):
        _overlap_once()
        return ds

    with _tr.span(_tele.SPAN_REALIGN_NATIVE_PREP):
        prep = native.realign_prep(
            b, md_buf, md_off, md_valid.astype(np.uint8), srows, goff,
            gen_consensus,
        )
    if prep is None:
        return None

    with _tr.span(_tele.SPAN_REALIGN_CONSENSUS):
        t_status = prep["t_status"]
        t_ref_off = prep["t_ref_off"]
        t_ref_start = prep["t_ref_start"]
        t_ref_end = prep["t_ref_end"]
        ref_all = prep["t_ref_buf"].tobytes().decode("ascii", "replace")
        r_group = prep["r_group"]
        r_row = prep["r_row"]
        r_dirty = prep["r_dirty"].astype(bool)
        r_md_set = prep["r_md_set"].astype(bool)
        r_orig = prep["r_orig_qual"]
        R = len(r_row)
        rg_off = np.searchsorted(r_group, np.arange(G + 1))
        c_group = prep["c_group"]
        cg_off = np.searchsorted(c_group, np.arange(G + 1))
        c_off = prep["c_seq_off"]
        c_all = prep["c_seq_buf"].tobytes().decode("ascii", "replace")
        c_is = prep["c_is"]
        c_ie = prep["c_ie"]

        rng = rng or random.Random(0)
        lengths = np.asarray(b.lengths).astype(np.int64)
        _log = logging.getLogger(__name__)

        # ---- per-group consensus finalize (sampling order == Python path) --
        # grp_cons[g] = list of (cons_str, index_start, index_end)
        grp_cons: list = [None] * G
        for g in range(G):
            if t_status[g] != 0:
                continue
            if rg_off[g + 1] == rg_off[g]:
                continue
            if consensus_model == "knowns" and known_indels is not None:
                from adam_tpu.models.positions import ReferenceRegion

                region_name = names[targets[int(gtid[g])].contig_idx]
                cons = [
                    (rec.consensus, rec.region.start, rec.region.end)
                    for rec in known_indels.get_indels_in_region(
                        ReferenceRegion(
                            region_name, int(t_ref_start[g]), int(t_ref_end[g])
                        )
                    )
                ]
            else:
                cons = [
                    (c_all[c_off[k]:c_off[k + 1]], int(c_is[k]), int(c_ie[k]))
                    for k in range(cg_off[g], cg_off[g + 1])
                ]
            # distinct (native path pre-dedupes the reads model; the knowns
            # model and the Python path share this exact dedup)
            seen = set()
            uniq = []
            for c in cons:
                if c not in seen:
                    seen.add(c)
                    uniq.append(c)
            cons = uniq
            if len(cons) > max_consensus_number:
                # random.sample on an index range picks the same positions
                # as sampling the list itself, preserving rng-state parity
                cons = [cons[j] for j in
                        rng.sample(range(len(cons)), max_consensus_number)]
            grp_cons[g] = cons

        # ---- build the spliced consensus sequences + (target, cons) pairs --
        # each pair tile sweeps <= rt reads of one target against one
        # consensus; tiles group by (off, rt) into fixed-shape GEMM batches
        cons_strs: list = []   # spliced full sequences, global ids
        grp_cons_base = np.zeros(G + 1, np.int64)
        for g in range(G):
            cons = grp_cons[g]
            grp_cons_base[g + 1] = (
                grp_cons_base[g] + (len(cons) if cons else 0)
            )
            if not cons:
                continue
            ref_start = int(t_ref_start[g])
            ref_end = int(t_ref_end[g])
            reference = ref_all[t_ref_off[g]:t_ref_off[g + 1]]
            for cs, cis, cie in cons:
                # Consensus.insert_into_reference (realign.py:612-620)
                if (cis < ref_start or cis > ref_end
                        or cie - 1 < ref_start or cie - 1 > ref_end):
                    raise ValueError("consensus and reference do not overlap")
                cons_strs.append(
                    reference[: cis - ref_start] + cs
                    + reference[cie - 1 - ref_start:]
                )

        # flat result layout: per group, ci-major [nc, nr]
        grp_task_base = np.zeros(G + 1, np.int64)
        for g in range(G):
            nr = int(rg_off[g + 1] - rg_off[g])
            nc = int(grp_cons_base[g + 1] - grp_cons_base[g])
            grp_task_base[g + 1] = grp_task_base[g] + nr * nc
        NT = int(grp_task_base[G])
        res_q = np.full(NT, np.inf, np.float32)
        res_o = np.full(NT, -1, np.int32)
        if NT:
            cons_lens = np.array([len(s) for s in cons_strs], np.int64)
            max_cl = int(cons_lens.max()) if len(cons_strs) else 1
            cons_mat = np.full(
                (len(cons_strs), max_cl), schema.BASE_PAD, np.uint8
            )
            for k, s in enumerate(cons_strs):
                cons_mat[k, : len(s)] = schema.encode_bases(s)

            # pair tiles: rt=16 for small targets, 128-read tiles for large
            p_res, p_n, p_cid, p_lo, p_off = [], [], [], [], []
            for g in range(G):
                cons = grp_cons[g]
                if not cons:
                    continue
                nr = int(rg_off[g + 1] - rg_off[g])
                rl_g = lengths[r_row[rg_off[g]:rg_off[g + 1]]]
                for ci in range(len(cons)):
                    cid = int(grp_cons_base[g]) + ci
                    clen = int(cons_lens[cid])
                    base = int(grp_task_base[g]) + ci * nr
                    for lo in range(0, nr, 128):
                        nrt = min(128, nr - lo)
                        need = clen - int(rl_g[lo:lo + nrt].min())
                        p_res.append(base + lo)
                        p_n.append(nrt)
                        p_cid.append(cid)
                        p_lo.append(int(rg_off[g]) + lo)
                        p_off.append(max(need, 1))
            p_res = np.asarray(p_res, np.int64)
            p_n = np.asarray(p_n, np.int32)
            p_cid = np.asarray(p_cid, np.int64)
            p_lo = np.asarray(p_lo, np.int64)
            p_rt = np.where(p_n <= 16, 16, 128).astype(np.int32)
            p_offb = _pow2_vec(p_off, 512).astype(np.int64)
            # intermediate 384 tier: WGS-shaped targets need 250-330 offsets,
            # and the sweep's im2col+GEMM cost scales linearly with the
            # padded off — the pow2 jump to 512 wasted ~40% on that band
            p_offb = np.where(
                (p_offb == 512) & (np.asarray(p_off) <= 384), 384, p_offb
            )

    pending = []  # (pair slice indices, device, lazy (best_q, best_o))
    with _tr.span(_tele.SPAN_REALIGN_SWEEP_DISPATCH):
        if NT:
            bases_np = np.asarray(b.bases)
            quals_np = np.asarray(b.quals)
            L = bases_np.shape[1]
            lr = int(_pow2_vec(np.array([max(int(lengths.max()), 1)]), 32)[0])
            cols = min(L, lr)

            # rows into the flat to_clean read index -> batch row, as i32
            r_row32 = r_row.astype(np.int32)
            # fan GEMM chunks across the pool/mesh devices (probe-paced
            # weighted round-robin, ROADMAP "realign sweep scheduling"):
            # until now every bucket dispatched to the default device while
            # the rest of the pool idled through the 1.31 s sweep net
            from adam_tpu.parallel.device_pool import putter as _putter

            _sched = None
            if sweep_devices is not None and len(sweep_devices) > 1:
                from adam_tpu.parallel.device_pool import SweepSchedule

                _sched = SweepSchedule(sweep_devices)
            key = p_offb * 1024 + p_rt
            border = np.argsort(key, kind="stable")
            ukeys, ustarts = np.unique(key[border], return_index=True)
            ustarts = np.append(ustarts, len(border))
            for u in range(len(ukeys)):
                seg = border[ustarts[u]:ustarts[u + 1]]
                off = int(ukeys[u] // 1024)
                rt = int(ukeys[u] % 1024)
                P = _sweep_gemm_P(off, rt)
                lc = off + lr
                for s in range(0, len(seg), P):
                    part = seg[s:s + P]
                    # chunk-local read block [P*rt, lr]: row j*rt+k is read k
                    # of pair j — no device gather, and the compiled shape is
                    # independent of the dataset size
                    rc = np.full((P * rt, lr), schema.BASE_PAD, np.uint8)
                    rq = np.zeros((P * rt, lr), np.uint8)
                    rl = np.zeros(P * rt, np.int32)
                    pm = np.zeros(P * rt, bool)
                    ct = np.full((P, lc), schema.BASE_PAD, np.uint8)
                    cl = np.zeros(P, np.int32)
                    for j, pi in enumerate(part):
                        nrt = int(p_n[pi])
                        lo = int(p_lo[pi])
                        rows_t = r_row32[lo:lo + nrt]
                        blk = slice(j * rt, j * rt + nrt)
                        rc[blk, :cols] = bases_np[rows_t, :cols]
                        rq[blk, :cols] = quals_np[rows_t, :cols]
                        rl[blk] = lengths[rows_t]
                        pm[blk] = True
                        cid = int(p_cid[pi])
                        cc = min(int(cons_lens[cid]), lc)
                        ct[j, :cc] = cons_mat[cid, :cc]
                        cl[j] = cons_lens[cid]
                    dev = _sched.next_device() if _sched is not None else None
                    _put = _putter(dev)  # commit + h2d transfer accounting
                    pending.append((part, dev, sweep_gemm_kernel(
                        _put(rc), _put(rq), _put(rl),
                        _put(pm), _put(ct), _put(cl),
                        off, rt, lr,
                    )))

    # host work hides under the device queue drain — genuinely
    # overlapped only when sweeps are actually in flight
    _overlap_once(in_dispatch=bool(pending))
    with _tr.span(_tele.SPAN_REALIGN_SWEEP_FETCH):
        if pending:
            # one fused fetch PER DEVICE: per-chunk fetches each pay a
            # device round trip, and chunks
            # committed to different pool devices cannot concatenate in
            # one computation — so each device's chunks fuse into one
            # drain through the transfer helper (d2h ledger + retry)
            from adam_tpu.utils.transfer import device_fetch as _dfetch

            groups: dict = {}
            for k, (_part, dev, _out) in enumerate(pending):
                gk = id(dev) if dev is not None else None
                groups.setdefault(gk, []).append(k)
            fetched_q: dict = {}
            fetched_o: dict = {}
            for idxs in groups.values():
                gq = np.asarray(_dfetch(jnp.concatenate(
                    [pending[k][2][0].reshape(-1) for k in idxs]
                )))
                go = np.asarray(_dfetch(jnp.concatenate(
                    [pending[k][2][1].reshape(-1) for k in idxs]
                )))
                pos = 0
                for k in idxs:
                    Pc, rtc = pending[k][2][0].shape
                    fetched_q[k] = gq[pos: pos + Pc * rtc].reshape(Pc, rtc)
                    fetched_o[k] = go[pos: pos + Pc * rtc].reshape(Pc, rtc)
                    pos += Pc * rtc
            for k, (part, _dev, _out) in enumerate(pending):
                q2, o2 = fetched_q[k], fetched_o[k]
                for j, pi in enumerate(part):
                    nrt = int(p_n[pi])
                    rb = int(p_res[pi])
                    res_q[rb:rb + nrt] = q2[j, :nrt]
                    res_o[rb:rb + nrt] = o2[j, :nrt]

    _overlap_once()  # NT == 0: nothing was dispatched, run it here
    with _tr.span(_tele.SPAN_REALIGN_DECIDE):
        # ---- scoring + rewrite decisions (numpy, one pass per group) -------
        new_batch = jax.tree.map(np.array, b)
        new_md: dict[int, Optional[str]] = {}
        new_attrs: dict[int, str] = {}
        cmax = new_batch.cmax

        # realigned-read accumulators (one native MD-move call at the end)
        ra_rows, ra_g, ra_off, ra_head, ra_midl, ra_mido, ra_end = (
            [], [], [], [], [], [], [])
        ra_start, ra_newend = [], []
        realigned_mask = np.zeros(R, bool)

        for g in range(G):
            cons = grp_cons[g]
            if not cons:
                continue
            nr = int(rg_off[g + 1] - rg_off[g])
            nc = len(cons)
            sl = slice(int(grp_task_base[g]), int(grp_task_base[g + 1]))
            # ci-major flat -> [nr, nc]
            q = res_q[sl].reshape(nc, nr).T
            o = res_o[sl].reshape(nc, nr).T
            orig = r_orig[rg_off[g]:rg_off[g + 1]].astype(np.int64)
            pre_total = int(orig.sum())
            use = q < orig[:, None]
            qi = np.zeros_like(q, dtype=np.int64)
            qi[use] = q[use].astype(np.int64)
            contrib = np.where(use, qi, orig[:, None])
            totals = contrib.sum(axis=0)
            best_ci = int(nc - 1 - np.argmin(totals[::-1]))
            best_total = int(totals[best_ci])
            lod = (pre_total - best_total) / 10.0
            ref_start = int(t_ref_start[g])
            ref_len = int(t_ref_off[g + 1] - t_ref_off[g])
            _log.debug(
                "On target %d [%d, %d), before realignment, sum was %d; "
                "best consensus %d has sum %d (LOD %.2f)",
                int(gtid[g]), ref_start, ref_start + ref_len, pre_total,
                best_ci, best_total, lod,
            )
            if lod <= lod_threshold:
                continue
            cons_str, cis, cie = cons[best_ci]
            best_map = np.where(use[:, best_ci], o[:, best_ci], -1)
            okm = best_map >= 0
            if not okm.any():
                continue
            ridx = np.flatnonzero(okm) + int(rg_off[g])
            om = best_map[okm].astype(np.int64)
            rows_g = r_row[ridx]
            Lr = lengths[rows_g]
            new_start = ref_start + om
            if cis == cie - 1:  # insertion
                id_len = len(cons_str)
                id_op = ord("I")
                end_len = Lr - id_len - (cis - new_start)
                end_pen = -id_len
            else:  # deletion
                id_len = cie - 1 - cis
                id_op = ord("D")
                end_len = Lr - (cis - new_start)
                end_pen = len(cons_str)
            head_len = cis - new_start
            three = (head_len > 0) & (end_len > 0)
            new_end = np.where(three, new_start + Lr + end_pen, new_start + Lr)
            keep = om + (new_end - new_start) <= ref_len
            if not keep.any():
                continue
            k = np.flatnonzero(keep)
            realigned_mask[ridx[k]] = True
            ra_rows.append(rows_g[k])
            ra_g.append(np.full(len(k), g, np.int32))
            ra_off.append(om[k])
            ra_head.append(
                np.where(three[k], head_len[k], Lr[k]).astype(np.int32)
            )
            ra_midl.append(np.where(three[k], id_len, 0).astype(np.int32))
            ra_mido.append(np.where(three[k], id_op, 0).astype(np.uint8))
            ra_end.append(np.where(three[k], end_len[k], 0).astype(np.int32))
            ra_start.append(new_start[k])
            ra_newend.append(new_end[k])

        # ---- write back: realigned rows ------------------------------------
        if ra_rows:
            rows_a = np.concatenate(ra_rows)
            g_a = np.concatenate(ra_g)
            off_a = np.concatenate(ra_off)
            head_a = np.concatenate(ra_head)
            midl_a = np.concatenate(ra_midl)
            mido_a = np.concatenate(ra_mido)
            end_a = np.concatenate(ra_end)
            start_a = np.concatenate(ra_start)
            newend_a = np.concatenate(ra_newend)
            moved = native.md_move_batch(
                b, rows_a, prep["t_ref_buf"], t_ref_off, g_a, off_a,
                head_a, midl_a, mido_a, end_a, start_a,
            )
            if moved is None:
                return None
            mbuf, moff = moved
            mstr = mbuf.tobytes().decode("ascii")

            three_a = mido_a != 0
            if three_a.any() and cmax < 3:
                raise ValueError("realigned cigar exceeds batch cmax")
            # OC/OP provenance from the pre-realignment columns
            oc = native.cigar_strings(
                np.asarray(b.cigar_ops)[rows_a],
                np.asarray(b.cigar_lens)[rows_a],
                np.asarray(b.cigar_n)[rows_a],
            )
            if oc is not None:
                oc_buf, oc_off = oc
                oc_all = oc_buf.tobytes().decode("ascii")
                old_cigs = [
                    oc_all[oc_off[k]:oc_off[k + 1]] for k in range(len(rows_a))
                ]
            else:
                old_cigs = [
                    schema.decode_cigar(
                        np.asarray(b.cigar_ops)[r],
                        np.asarray(b.cigar_lens)[r],
                        int(np.asarray(b.cigar_n)[r]),
                    )
                    for r in rows_a
                ]
            attrs_col = StringColumn.of(side.attrs)
            old_starts = np.asarray(b.start)[rows_a]
            for k, row in enumerate(rows_a):
                row = int(row)
                tag = f"OC:Z:{old_cigs[k]}\tOP:i:{int(old_starts[k]) + 1}"
                cur = attrs_col[row] or ""
                new_attrs[row] = cur + "\t" + tag if cur else tag
                new_md[row] = mstr[moff[k]:moff[k + 1]]
            ops_new = np.zeros((len(rows_a), cmax), np.uint8)
            ops_new[:] = schema.CIGAR_PAD
            lens_new = np.zeros((len(rows_a), cmax), np.int32)
            ncig_new = np.where(three_a, 3, 1).astype(np.int32)
            ops_new[:, 0] = schema.CIGAR_M
            lens_new[:, 0] = head_a
            if three_a.any() and cmax >= 3:
                ops_new[three_a, 1] = np.where(
                    mido_a[three_a] == ord("I"), schema.CIGAR_I, schema.CIGAR_D
                )
                lens_new[three_a, 1] = midl_a[three_a]
                ops_new[three_a, 2] = schema.CIGAR_M
                lens_new[three_a, 2] = end_a[three_a]
            new_batch.cigar_ops[rows_a] = ops_new
            new_batch.cigar_lens[rows_a] = lens_new
            new_batch.cigar_n[rows_a] = ncig_new
            new_batch.start[rows_a] = start_a
            new_batch.end[rows_a] = newend_a
            new_batch.mapq[rows_a] = np.asarray(b.mapq)[rows_a] + 10

        # ---- write back: dirty (left-normalized) non-realigned rows --------
        dirty_idx = np.flatnonzero(r_dirty & ~realigned_mask)
        if len(dirty_idx):
            cig_off = prep["r_cigar_off"]
            cig_all = prep["r_cigar_buf"].tobytes().decode("ascii")
            md_off2 = prep["r_md_off"]
            md_all = prep["r_md_buf"].tobytes().decode("ascii")
            for i in dirty_idx:
                row = int(r_row[i])
                cig = cig_all[cig_off[i]:cig_off[i + 1]]
                elems = parse_cigar(cig)
                ops, lens_, ncig = schema.encode_cigar(
                    cig, max(cmax, len(elems))
                )
                if ncig > cmax:
                    raise ValueError("realigned cigar exceeds batch cmax")
                new_batch.cigar_ops[row] = ops[:cmax]
                new_batch.cigar_lens[row] = lens_[:cmax]
                new_batch.cigar_n[row] = ncig
                new_batch.end[row] = int(new_batch.start[row]) + cigar_ref_len(
                    elems
                )
                if r_md_set[i]:
                    new_md[row] = md_all[md_off2[i]:md_off2[i + 1]]

        new_side = dc_replace(
            side,
            md=with_overrides(StringColumn.of(side.md), new_md),
            attrs=with_overrides(StringColumn.of(side.attrs), new_attrs),
        )
    return ds.with_batch(new_batch, new_side)


def warm_sweep_shapes(offs=(384, 512, 1024, 2048, 4096), rts=(16, 128),
                      lr: int = 128):
    """Compile the GEMM sweep tiers ahead of a timed run.

    Shapes depend only on the static (off, rt, lr) tier — never on
    dataset size — so a handful of dummy dispatches covers everything a
    real run can hit (each missed shape is a cold compile inside the
    timed run).  The off tiers must span
    ``pow2(max_target_size + 2*read_len + max_indel_size)`` (~3700 under
    default knobs -> 4096); ``lr`` is ``pow2(max read length)`` of the
    data the timed run will see.  Returns the number of shapes warmed."""
    n = 0
    for off in offs:
        for rt in rts:
            P = _sweep_gemm_P(off, rt)
            lc = off + lr
            bq, _ = sweep_gemm_kernel(
                jnp.zeros((P * rt, lr), jnp.uint8),
                jnp.zeros((P * rt, lr), jnp.uint8),
                jnp.zeros(P * rt, jnp.int32),
                jnp.zeros(P * rt, bool),
                jnp.zeros((P, lc), jnp.uint8),
                jnp.zeros(P, jnp.int32),
                off, rt, lr,
            )
            jax.block_until_ready(bq)
            n += 1
    return n


def candidate_mask(b, targets, names) -> np.ndarray:
    """bool[N]: rows mapped to a realignment target — THE membership
    rule every pipeline's split/re-split/observe must share."""
    return map_batch_to_targets(b, targets, names) >= 0


def mask_out_candidates(ds, targets, names, mask=None):
    """Remainder view of a window/shard: candidate rows masked invalid
    (no keep-side copy; the Parquet encoder and the observe walk both
    filter on ``valid``).  Pass a cached ``mask`` to skip recomputing
    the target mapping."""
    b = ds.batch.to_numpy()
    if mask is None:
        mask = candidate_mask(b, targets, names)
    if not mask.any():
        return ds
    return ds.with_batch(b.replace(valid=np.asarray(b.valid) & ~mask))


def split_realign_candidates(ds, targets, names):
    """Split a window/shard into (candidates, writable remainder).

    Candidate rows (mapped to a realignment target) gather into a new
    dataset; the ~87%% keep-side majority is returned MASKED (valid
    cleared) rather than copied — the Parquet encoder's own row gather
    filters it once at write time.  Shared by the streamed and sharded
    pipelines so their split semantics cannot diverge.  Returns
    (candidates-or-None, remainder, n_remaining_valid)."""
    b = ds.batch.to_numpy()
    cand = candidate_mask(b, targets, names)
    if cand.any():
        candidates = ds.take_rows(np.flatnonzero(cand))
        ds = mask_out_candidates(ds, targets, names, mask=cand)
    else:
        candidates = None
    return candidates, ds, int(np.asarray(ds.batch.valid).sum())
