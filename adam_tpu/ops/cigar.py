"""Batched CIGAR walks on device.

The reference walks htsjdk Cigar objects per read on the JVM
(``rich/RichAlignmentRecord.scala``: referenceLengthFromCigar :41-57,
unclippedStart/End :110-121, fivePrimePosition :124-126, per-base
referencePositions :200-229).  Here every walk is a masked reduction over
the ``[N, C]`` cigar columns, so one XLA fusion covers the whole batch.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from adam_tpu.formats import schema


def _op_table(table):
    return jnp.asarray(table)


def _valid_mask(cigar_ops, cigar_n):
    C = cigar_ops.shape[-1]
    return jnp.arange(C) < cigar_n[..., None]


def reference_length(cigar_ops, cigar_lens, cigar_n):
    """Reference bases consumed by each read's CIGAR (M/D/N/=/X)."""
    consumes = _op_table(schema.CIGAR_CONSUMES_REF)[cigar_ops]
    v = _valid_mask(cigar_ops, cigar_n)
    return jnp.sum(cigar_lens * consumes * v, axis=-1).astype(jnp.int64)


def query_length(cigar_ops, cigar_lens, cigar_n):
    """Query bases consumed (M/I/S/=/X)."""
    consumes = _op_table(schema.CIGAR_CONSUMES_QUERY)[cigar_ops]
    v = _valid_mask(cigar_ops, cigar_n)
    return jnp.sum(cigar_lens * consumes * v, axis=-1).astype(jnp.int32)


def _is_clip(cigar_ops):
    return (cigar_ops == schema.CIGAR_S) | (cigar_ops == schema.CIGAR_H)


def leading_clip(cigar_ops, cigar_lens, cigar_n):
    """Total clipped (S+H) length at the start of each read."""
    v = _valid_mask(cigar_ops, cigar_n)
    clip = _is_clip(cigar_ops) & v
    run = jnp.cumprod(clip.astype(jnp.int32), axis=-1)  # 1 while still clipping
    return jnp.sum(cigar_lens * run, axis=-1).astype(jnp.int64)


def trailing_clip(cigar_ops, cigar_lens, cigar_n):
    """Total clipped (S+H) length at the end of each read.

    Padding lanes (beyond cigar_n) must not break the trailing run, so the
    run predicate is clip-or-pad, and only real clip lanes contribute."""
    v = _valid_mask(cigar_ops, cigar_n)
    clip = _is_clip(cigar_ops) & v
    run_pred = (clip | ~v).astype(jnp.int32)
    run = jnp.flip(jnp.cumprod(jnp.flip(run_pred, axis=-1), axis=-1), axis=-1)
    return jnp.sum(cigar_lens * clip * run, axis=-1).astype(jnp.int64)


def unclipped_start(start, cigar_ops, cigar_lens, cigar_n):
    """start - leading clips (RichAlignmentRecord.unclippedStart)."""
    return start - leading_clip(cigar_ops, cigar_lens, cigar_n)


def unclipped_end(end, cigar_ops, cigar_lens, cigar_n):
    """end + trailing clips.  ``end`` is 0-based exclusive, and so is the
    reference's unclippedEnd (it folds clip lengths onto the exclusive
    ``getEnd``, rich/RichAlignmentRecord.scala:110-114) — no -1 anywhere."""
    return end + trailing_clip(cigar_ops, cigar_lens, cigar_n)


def five_prime_position(start, end, flags, cigar_ops, cigar_lens, cigar_n):
    """5' reference position with clipping (fivePrimePosition semantics,
    rich/RichAlignmentRecord.scala:124-126): the *exclusive* unclipped end
    for reverse-strand reads — the reference uses `end` directly, which is
    0-based exclusive — and the unclipped start otherwise.

    Duplicate marking keys on this (ReferencePositionPair via
    RichAlignmentRecord.fivePrimeReferencePosition); the key also carries
    strand, so forward/reverse positions never collide."""
    rev = (flags & schema.FLAG_REVERSE) != 0
    us = unclipped_start(start, cigar_ops, cigar_lens, cigar_n)
    ue = unclipped_end(end, cigar_ops, cigar_lens, cigar_n)
    return jnp.where(rev, ue, us)


def five_prime_position_np(start, end, flags, cigar_ops, cigar_lens, cigar_n):
    """Host (numpy) twin of :func:`five_prime_position` -> i64[N].

    Pipelines whose only device work would be this walk plus a couple of
    reductions (duplicate marking's key prep) run it host-side, where
    it needs no transfer at all.
    """
    import numpy as np

    ops = np.asarray(cigar_ops)
    lens = np.asarray(cigar_lens).astype(np.int64)
    n_ops = np.asarray(cigar_n)
    N, C = ops.shape if ops.ndim == 2 else (len(n_ops), 0)
    if C == 0:
        return np.asarray(start).copy()
    v = np.arange(C)[None, :] < n_ops[:, None]
    clip = ((ops == schema.CIGAR_S) | (ops == schema.CIGAR_H)) & v
    lead_run = np.cumprod(clip.astype(np.int64), axis=1)
    lead = (lens * lead_run).sum(axis=1)
    run_pred = (clip | ~v).astype(np.int64)
    trail_run = np.cumprod(run_pred[:, ::-1], axis=1)[:, ::-1]
    trail = (lens * clip * trail_run).sum(axis=1)
    rev = (np.asarray(flags) & schema.FLAG_REVERSE) != 0
    return np.where(rev, np.asarray(end) + trail, np.asarray(start) - lead)


def first_real_op(cigar_ops, cigar_n):
    """Code of the first non-clip op, CIGAR_PAD if none."""
    C = cigar_ops.shape[-1]
    v = _valid_mask(cigar_ops, cigar_n)
    real = v & ~_is_clip(cigar_ops)
    idx = jnp.argmax(real, axis=-1)
    any_real = jnp.any(real, axis=-1)
    got = jnp.take_along_axis(cigar_ops, idx[..., None], axis=-1)[..., 0]
    return jnp.where(any_real, got, schema.CIGAR_PAD)


def reference_positions_np(cigar_ops, cigar_lens, cigar_n, start, lmax):
    """Host (numpy) twin of :func:`reference_positions` -> i64[N, lmax].

    Pipelines that need per-base reference positions as a *host-side*
    filter input (e.g. BQSR's known-SNP masking) use this to avoid
    round-tripping an int64 [N, L] array through the device.
    Delegates to the threaded native CIGAR walk when available.
    """
    import numpy as np

    from adam_tpu import native

    nat = native.ref_positions(cigar_ops, cigar_lens, cigar_n, start, lmax)
    if nat is not None:
        return nat

    ops = np.asarray(cigar_ops)
    lens = np.asarray(cigar_lens).astype(np.int64)
    n_ops = np.asarray(cigar_n)
    start = np.asarray(start)
    N, C = ops.shape
    if C == 0:
        return np.full((N, lmax), -1, np.int64)
    v = (np.arange(C)[None, :] < n_ops[:, None]).astype(np.int64)
    consumes_q = schema.CIGAR_CONSUMES_QUERY[np.minimum(ops, 15)].astype(np.int64)
    consumes_r = schema.CIGAR_CONSUMES_REF[np.minimum(ops, 15)].astype(np.int64)
    qlen = lens * consumes_q * v
    rlen = lens * consumes_r * v
    q_end = np.cumsum(qlen, axis=1)
    q0 = q_end - qlen
    r0 = np.cumsum(rlen, axis=1) - rlen
    aligned = (consumes_q * consumes_r * v).astype(bool)

    j = np.arange(lmax, dtype=np.int64)
    # first op whose query span ends after j: vectorized binary search
    # (side='right') over the non-decreasing q_end lanes, [N, L] working set
    lo = np.zeros((N, lmax), np.int64)
    hi = np.full((N, lmax), C, np.int64)
    while (lo < hi).any():
        mid = (lo + hi) // 2
        ge = np.take_along_axis(q_end, np.minimum(mid, C - 1), axis=1) <= j[None, :]
        adv = lo < hi
        lo = np.where(adv & ge, mid + 1, lo)
        hi = np.where(adv & ~ge, mid, hi)
    op_idx = lo
    in_read = op_idx < C
    op_clip = np.minimum(op_idx, C - 1)
    hit = np.take_along_axis(aligned, op_clip, axis=1) & in_read
    pos = (
        start[:, None]
        + np.take_along_axis(r0, op_clip, axis=1)
        + (j[None, :] - np.take_along_axis(q0, op_clip, axis=1))
    )
    return np.where(hit, pos, -1)


def reference_positions(cigar_ops, cigar_lens, cigar_n, start, lmax):
    """Per-base reference position for each read -> i64[N, lmax].

    -1 for bases that don't map to the reference (insertions, soft clips)
    and for padding lanes — the role of
    RichAlignmentRecord.referencePositions (:200-229).

    Implemented as a per-base binary search over the cigar's cumulative
    query spans (searchsorted over the [C] lane axis), so the working set
    stays [N, L] — no [N, C, L] blow-up, and the fusion compiles in
    milliseconds even under x64.
    """
    consumes_q = _op_table(schema.CIGAR_CONSUMES_QUERY)[cigar_ops]
    consumes_r = _op_table(schema.CIGAR_CONSUMES_REF)[cigar_ops]
    v = _valid_mask(cigar_ops, cigar_n).astype(jnp.int64)
    qlen = cigar_lens * consumes_q * v  # query span per op
    rlen = cigar_lens * consumes_r * v
    q_end = jnp.cumsum(qlen, axis=-1)  # query offset at op end
    q0 = q_end - qlen
    r0 = jnp.cumsum(rlen, axis=-1) - rlen  # ref offset at op start
    aligned = (consumes_q * consumes_r * v).astype(bool)  # M/=/X

    j = jnp.arange(lmax, dtype=q_end.dtype)  # [L]
    # first op whose query span ends after j (ops with qlen==0 share q_end
    # with their predecessor, so side='right' skips them)
    op_idx = jax.vmap(lambda qe: jnp.searchsorted(qe, j, side="right"))(q_end)
    C = cigar_ops.shape[-1]
    in_read = op_idx < C
    op_idx = jnp.minimum(op_idx, C - 1)
    hit = jnp.take_along_axis(aligned, op_idx, axis=-1) & in_read
    pos = (
        start[..., None]
        + jnp.take_along_axis(r0, op_idx, axis=-1)
        + (j[None, :] - jnp.take_along_axis(q0, op_idx, axis=-1))
    )
    return jnp.where(hit, pos, -1)
