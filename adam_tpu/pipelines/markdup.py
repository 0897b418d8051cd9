"""Duplicate marking.

Picard-style semantics matching ``rdd/read/MarkDuplicates.scala:66-128``:

1. Bucket reads by (record group, read name) — SingleReadBucket
   (models/SingleReadBucket.scala:30-42).
2. Key each bucket by its 5'-clipped position pair —
   ReferencePositionPair (models/ReferencePositionPair.scala:30-52):
   read1 position is the first first-of-pair read's 5' position (strand
   included); unmapped reads key by their *sequence* so identical
   unplaced pairs group; fragments have no read2 position.
3. Group by (library, left position); within a group, subgroup by right
   position; in each pair-subgroup keep the highest bucket score
   (sum of quals >= 15 over primary reads, :45-47) unmarked — its
   secondaries are still marked — and mark everything else; a
   fragment-subgroup is wholly marked when pair-subgroups co-exist at the
   same left position; unmapped reads are never marked.

TPU formulation: 5' keys and bucket scores are vectorized per-window
(masked CIGAR walks + masked segment sums) so they pipeline with ingest;
the group-subgroup-argmax cascade is one lexsort + run-boundary scan
over the *global* bucket table (no hash shuffles).  The split is the
same shape the sharded path uses: compact per-row summaries travel,
[N, L] matrices never do.  No per-read Python anywhere.
"""

from __future__ import annotations

import threading

import numpy as np

from adam_tpu.api.datasets import AlignmentDataset
from adam_tpu.formats import schema
from adam_tpu.formats.strings import StringColumn
from adam_tpu.ops import cigar as cigar_ops
from adam_tpu.utils.transfer import device_fetch


def markdup_columns_local(
    start, end, flags, ops, lens, n_ops, quals, lengths
):
    """[N, L] duplicate-marking reductions for one (device-resident)
    batch slice -> (five_prime i64[N], score i32[N]).

    Traceable body shared by the single-chip jit wrapper below and the
    mesh ``shard_map`` variant (parallel/dist.distributed_markdup) — the
    5'-clipped key via the device CIGAR walk, the bucket score via a
    masked segment sum.  Only these compact per-row columns ever cross
    the device link; the group-subgroup-argmax cascade stays host-side.
    """
    import jax.numpy as jnp

    five = cigar_ops.five_prime_position(start, end, flags, ops, lens, n_ops)
    in_read = jnp.arange(quals.shape[1])[None, :] < lengths[:, None]
    score = jnp.where(in_read & (quals >= 15), quals, 0).sum(
        axis=1, dtype=jnp.int32
    )
    return five, score


_COLUMNS_JITS: dict = {}  # donate -> lazily-built module-level jit
_COLUMNS_JIT_LOCK = threading.Lock()


def get_columns_jit(donate: bool = False):
    """The module-level jit of :func:`markdup_columns_local` (built
    lazily; shared by the dispatch below and the device pool's prewarm
    so both hit the same executable cache).  Locked: the prewarm calls
    this from one thread per device, and a lost race here would warm a
    discarded wrapper whose executable cache the real dispatches never
    see.  ``donate=True`` donates the per-call ``start`` temporary
    (i64[g], the only input whose buffer the i64[g] ``five`` output can
    alias; quals/lengths/flags come from the window's device handle and
    stay) — dispatched only where ``device_pool.donation_ok`` says the
    runtime honors it, and warmed by the same decision."""
    key = bool(donate)
    jit = _COLUMNS_JITS.get(key)
    if jit is None:
        with _COLUMNS_JIT_LOCK:
            jit = _COLUMNS_JITS.get(key)
            if jit is None:
                import jax

                jit = jax.jit(
                    markdup_columns_local,
                    **({"donate_argnums": (0,)} if donate else {}),
                )
                _COLUMNS_JITS[key] = jit
    return jit


def markdup_columns_dispatch(batch, device=None, mesh=None, resident=None):
    """Dispatch the [N, L] markdup reductions on a device -> lazy
    (five, score) device arrays for the batch's real rows.

    Row-padded to the pow2 grid so the compile cache sees a handful of
    shapes; the streamed pipeline dispatches window i+1 here while
    window i's columns are being fetched/summarized (double buffer).
    ``device``: an explicit jax device to commit the inputs to (the
    multi-chip pool's round-robin target); ``None`` keeps the default
    device, exactly the single-chip behavior.  ``resident``: the
    window's device payload (``device_pool.ResidentWindow``, pinned at
    ingest or placed for the pass) — quals/lengths/flags dispatch
    straight off the handle and only the markdup-specific start/end/
    cigar columns ship.  ``mesh``: a
    :class:`~adam_tpu.parallel.partitioner.MeshPartitioner` with a
    mesh-sharded ``resident`` — the window shards over its ``batch``
    axis and every device works the same window (SPMD), bitwise the
    single-chip columns.  Without a handle (the in-memory API) the call
    places the row arrays itself."""
    from adam_tpu.formats.batch import (
        grid_cigar_cols, grid_cols, grid_rows, pad_rows_np,
    )
    from adam_tpu.parallel.device_pool import (
        donation_ok, putter, resident_arrays, span_attrs,
    )
    from adam_tpu.utils import compile_ledger, faults
    from adam_tpu.utils import retry as _retry
    from adam_tpu.utils import telemetry as _tele

    _put = putter(device)
    attrs = {"device": "mesh"} if mesh is not None else span_attrs(device)
    with _tele.TRACE.span(
        _tele.SPAN_MD_COLUMNS, backend="device",
        reads=int(batch.n_rows), **attrs,
    ):
        b = batch.to_numpy()
        n = b.n_rows
        # quantize BOTH axes, not just rows: windows differ in lmax and
        # max cigar-op count, and every distinct shape is a fresh
        # trace+compile serialized inside pass A's ingest loop (the
        # walks mask by lengths/cigar_n, so the padding lanes are inert)
        gl = grid_cols(b.lmax)
        gc = grid_cigar_cols(
            b.cigar_ops.shape[1] if b.cigar_ops.ndim == 2 else 1
        )
        rw = resident
        g = rw.g if rw is not None else grid_rows(n)

        def fresh():
            return (
                pad_rows_np(b.start, g, -1),
                pad_rows_np(b.end, g, -1),
                pad_rows_np(b.cigar_ops, g, schema.CIGAR_PAD, cols=gc),
                pad_rows_np(b.cigar_lens, g, 0, cols=gc),
                pad_rows_np(b.cigar_n, g, 0),
            )

        if mesh is not None:
            if rw is None:
                raise ValueError("the mesh markdup dispatches on a "
                                 "resident window handle")

            def dispatch_mesh():
                faults.point("device.dispatch")
                return mesh.markdup_window_resident(rw, fresh())

            with compile_ledger.track(
                ("mesh.markdup", g, gc, gl), mesh.ledger_key()
            ):
                five, score = _retry.retry_call(
                    dispatch_mesh, site="markdup.dispatch"
                )
            return five[:n], score[:n]

        def dispatch():
            # the device_put + jit call re-run as one unit on a retry
            # (device_put is idempotent — a fresh commit; the donated
            # start temporary is re-placed every attempt, so a half-run
            # donating call can never re-pass a dead buffer)
            faults.point("device.dispatch", device=device)
            start, end, ops, lens, n_ops = (_put(a) for a in fresh())
            rows = (
                rw.args() if rw is not None
                else resident_arrays(b, g, gl, _put)[0]
            )
            _bases, quals, lengths, flags, _rg = rows
            return get_columns_jit(donate=donation_ok(device))(
                start, end, flags, ops, lens, n_ops, quals, lengths,
            )

        # compile-ledger key == the prewarm entry key for this kernel:
        # a miss here is a shape the prewarm never covered, cold-
        # compiling inside pass A's ingest loop
        with compile_ledger.track(("markdup.columns", g, gc, gl), device):
            five, score = _retry.retry_call(
                dispatch, site="markdup.dispatch"
            )
        return five[:n], score[:n]


def markdup_columns_device(batch):
    """Blocking variant of :func:`markdup_columns_dispatch` -> host
    (five i64[N], score i32[N])."""
    five, score = markdup_columns_dispatch(batch)
    return device_fetch(five), device_fetch(score)


def _sequence_hashes(bases: np.ndarray, lengths: np.ndarray) -> np.ndarray:
    """Deterministic per-read sequence hash (unmapped-read grouping key).

    Polynomial over base codes; identical sequences (incl. length) hash
    equal — the role of the reference's sequence hashCode key for
    unplaced pairs (models/ReferencePositionPair.scala:43-51).
    """
    n, L = bases.shape
    rng = np.random.default_rng(0xADA5)
    w = rng.integers(1, 2**62, size=L, dtype=np.int64) | 1
    codes = bases.astype(np.int64) + 1
    h = (codes * w[None, :]).sum(axis=1)
    h = h ^ (lengths.astype(np.int64) * np.int64(0x9E3779B97F4A7C15 - (1 << 64)))
    return h & 0x7FFFFFFFFFFFFFFF


def row_summary(ds: AlignmentDataset, b=None, five_prime=None,
                score=None) -> dict:
    """Compact per-row duplicate-marking summary (host numpy).

    Everything :func:`resolve_duplicates` needs, and nothing [N, L]-
    shaped except the transient masked reductions here: the 5'-clipped
    position, the quality score, the row key columns, the bucket key
    inputs (read-group, name bytes), and the library id.  Windows of a
    streamed ingest each produce one of these; :func:`concat_summaries`
    splices them for the global resolve.  Pass ``b`` when the batch is
    already fetched to numpy — a device-resident batch is copied across
    the link exactly once — and ``five_prime``/``score`` when the [N, L]
    reductions already ran on the mesh (parallel/dist.distributed_markdup).
    """
    if b is None:
        b = ds.batch.to_numpy()
    n = b.n_rows
    if five_prime is None:
        five_prime = cigar_ops.five_prime_position_np(
            b.start, b.end, b.flags, b.cigar_ops, b.cigar_lens, b.cigar_n
        )
    if score is None:
        quals = np.asarray(b.quals)
        in_read = np.arange(b.lmax)[None, :] < np.asarray(b.lengths)[:, None]
        score = np.where(in_read & (quals >= 15), quals, 0).sum(
            axis=1, dtype=np.int32
        )

    flags = np.asarray(b.flags)
    valid = np.asarray(b.valid)
    mapped = (flags & schema.FLAG_UNMAPPED) == 0

    # per-row candidate keys (ReferencePositionPair.apply):
    # (kind, contig_or_hash, pos, strand); kind 0 = none, 1 = mapped
    # position, 2 = sequence-keyed (unmapped).  Only unmapped rows
    # consume the sequence hash — skip the O(N*L) polynomial for the
    # (typical) mostly-mapped batch.
    seq_hash = np.zeros(n, dtype=np.int64)
    um = np.flatnonzero(~mapped)
    if len(um):
        seq_hash[um] = _sequence_hashes(
            np.asarray(b.bases)[um], np.asarray(b.lengths)[um]
        )
    reverse = (flags & schema.FLAG_REVERSE) != 0
    row_key = np.zeros((n, 4), dtype=np.int64)
    row_key[:, 0] = np.where(mapped, 1, 2)
    row_key[:, 1] = np.where(mapped, np.asarray(b.contig_idx), seq_hash)
    row_key[:, 2] = np.where(mapped, five_prime, 0)
    row_key[:, 3] = np.where(mapped, reverse.astype(np.int64), 0)

    lib_ids = (
        ds.read_groups.library_ids()
        if len(ds.read_groups)
        else np.array([], np.int32)
    )
    rgidx = np.asarray(b.read_group_idx)
    lib_per_row = np.where(
        rgidx >= 0,
        lib_ids[np.clip(rgidx, 0, None)] if len(lib_ids) else -1,
        -1,
    ).astype(np.int64)

    return dict(
        flags=flags,
        valid=valid,
        score=score,
        row_key=row_key,
        rg_idx=rgidx.astype(np.int64),
        lib_per_row=lib_per_row,
        name_bytes=StringColumn.of(ds.sidecar.names).to_fixed_bytes(),
    )


def concat_summaries(parts: list[dict]) -> dict:
    """Splice window summaries into one global summary (names re-padded
    to a common byte width so the fixed-width unique stays exact)."""
    if len(parts) == 1:
        return parts[0]
    w = max(p["name_bytes"].dtype.itemsize for p in parts)
    dt = np.dtype(f"S{max(w, 1)}")
    out = {}
    for k in parts[0]:
        cols = [p[k] for p in parts]
        if k == "name_bytes":
            cols = [c.astype(dt) for c in cols]
        out[k] = np.concatenate(cols)
    return out


def _unique_inverse_fixed_bytes(names: np.ndarray) -> np.ndarray:
    """``np.unique(names, return_inverse=True)[1]`` for fixed-width byte
    names, via big-endian integer views when the width allows.

    memcmp order on null-padded fixed-width bytes == numeric order of
    the big-endian word(s), so the inverse ids are IDENTICAL to the
    S-dtype unique's — just ~4x faster (integer radix-ish sort instead
    of string compares; this was the single hottest step of the global
    duplicate resolve on a 1M-read input)."""
    n = len(names)
    w = names.dtype.itemsize
    if n == 0 or w > 16:
        return np.unique(names, return_inverse=True)[1]
    nw = 8 if w <= 8 else 16
    padded = np.zeros((n, nw), np.uint8)
    padded[:, :w] = names.view(np.uint8).reshape(n, w)
    words = padded.view(">u8").astype(np.uint64)
    if nw == 8:
        return np.unique(words[:, 0], return_inverse=True)[1]
    hi, lo = words[:, 0], words[:, 1]
    order = np.lexsort((lo, hi))
    sh, sl = hi[order], lo[order]
    new = np.ones(n, bool)
    new[1:] = (sh[1:] != sh[:-1]) | (sl[1:] != sl[:-1])
    inv = np.empty(n, np.int64)
    inv[order] = np.cumsum(new) - 1
    return inv


def resolve_duplicates(s: dict, sort_device=None,
                       sort_info: dict | None = None) -> np.ndarray:
    """Global group-subgroup-argmax cascade over row summaries -> bool[N]
    duplicate mask.  One lexsort over the bucket table; row order across
    windows is the tie-break order, matching the reference's partition
    concatenation.

    ``sort_device`` routes the 9-key lexsort cascade — the measured
    1.56 s pure-host serial stage of the streamed barrier (BENCH_r05
    ``resolve_s``) — through the device sort of the packed summary keys
    (:func:`adam_tpu.parallel.dist.device_lexsort`; bitwise the host
    permutation, host fallback on any failure).  ``None`` keeps the
    host ``np.lexsort``; pass a jax device (the pool/mesh's device 0)
    or the string ``"default"`` for the default device.  ``sort_info``
    receives ``{"device_sort": bool}`` — whether the device sort
    actually delivered (False on its internal host fallback), so the
    caller's telemetry reports the outcome, not the request."""
    flags = s["flags"]
    valid = s["valid"]
    n = len(flags)
    if n == 0:
        return np.zeros(0, dtype=bool)

    # ----- bucket ids: dense (rg, name) -> id (SingleReadBucket) -------
    names = s["name_bytes"]
    name_inv = _unique_inverse_fixed_bytes(names)
    rg = s["rg_idx"]
    key = (rg + 1) * (name_inv.max() + 1 if len(name_inv) else 1) + name_inv
    key = np.where(valid, key, -1)
    vrows = np.flatnonzero(valid)
    uniq, inv = np.unique(key[vrows], return_inverse=True)
    bucket_of = np.full(n, -1, dtype=np.int64)
    bucket_of[vrows] = inv
    n_buckets = len(uniq)
    if n_buckets == 0:
        return np.zeros(n, dtype=bool)

    mapped = (flags & schema.FLAG_UNMAPPED) == 0
    primary = (flags & (schema.FLAG_SECONDARY | schema.FLAG_SUPPLEMENTARY)) == 0
    first = (flags & schema.FLAG_FIRST_OF_PAIR) != 0
    second = (flags & schema.FLAG_SECOND_OF_PAIR) != 0
    row_key = s["row_key"]
    read_score = s["score"]

    in_bucket = bucket_of >= 0
    candidate = in_bucket & (((mapped & primary)) | ~mapped)

    # ordering inside a bucket: mapped-primary candidates first, then row
    # order (the reference's primaryMapped ++ unmapped concatenation)
    prio = (~mapped).astype(np.int64) * n + np.arange(n, dtype=np.int64)
    BIG = np.int64(2) * n * n + n

    def first_row(mask: np.ndarray) -> np.ndarray:
        """Per-bucket row with minimal prio among masked rows (-1 none)."""
        sel = np.full(n_buckets, BIG, dtype=np.int64)
        rows = np.flatnonzero(mask)
        np.minimum.at(sel, bucket_of[rows], prio[rows])
        out = np.where(sel < BIG, sel % n, -1)
        return out

    first_sel = first_row(candidate & first)
    second_sel = first_row(candidate & second)
    frag_sel = first_row(candidate)

    # bucket score: sum of primary-mapped read scores
    bucket_score = np.zeros(n_buckets, dtype=np.int64)
    sc_rows = np.flatnonzero(in_bucket & valid & mapped & primary)
    np.add.at(bucket_score, bucket_of[sc_rows], read_score[sc_rows].astype(np.int64))

    # library per bucket (library of the first read, in row order)
    lib_per_row = s["lib_per_row"]
    lead = first_row(in_bucket)
    bucket_lib = np.where(lead >= 0, lib_per_row[np.clip(lead, 0, None)], -1)

    # ----- per-bucket left/right keys ----------------------------------
    has_pair = (first_sel >= 0) | (second_sel >= 0)
    left_arr = np.zeros((n_buckets, 4), dtype=np.int64)
    right_arr = np.zeros((n_buckets, 4), dtype=np.int64)
    lk_rows = np.where(has_pair, first_sel, frag_sel)
    use_lk = lk_rows >= 0
    left_arr[use_lk] = row_key[lk_rows[use_lk]]
    rk_rows = np.where(has_pair, second_sel, -1)
    use_rk = rk_rows >= 0
    right_arr[use_rk] = row_key[rk_rows[use_rk]]

    # ----- group by (library, left), subgroup by right, mark -----------
    # lexicographic order (lib, L0..L3, R0..R3) with adjacent small-range
    # fields packed into shared words: kind < 4, strand < 2, and
    # |pos| < 2^40, so (lib<<2)|kind, (Lpos<<3)|(Lstrand<<2)|Rkind and
    # (Rpos<<1)|Rstrand preserve the 9-key order in 5 stable sorts
    # (full-range int64 hash keys L1/R1 stay unpacked)
    k1 = (bucket_lib << 2) | left_arr[:, 0]
    k3 = (left_arr[:, 2] << 3) | (left_arr[:, 3] << 2) | right_arr[:, 0]
    k5 = (right_arr[:, 2] << 1) | right_arr[:, 3]
    sort_keys = (k5, right_arr[:, 1], k3, left_arr[:, 1], k1)
    if sort_device is not None:
        from adam_tpu.parallel.dist import device_lexsort

        group_order = device_lexsort(
            sort_keys,
            None if sort_device == "default" else sort_device,
            info=sort_info,
        )
    else:
        if sort_info is not None:
            sort_info["device_sort"] = False
        group_order = np.lexsort(sort_keys)
    go = group_order
    sl = np.concatenate([bucket_lib[go, None], left_arr[go]], axis=1)
    sr = right_arr[go]
    new_left = np.ones(len(go), dtype=bool)
    new_left[1:] = (sl[1:] != sl[:-1]).any(axis=1)
    new_right = new_left.copy()
    new_right[1:] |= (sr[1:] != sr[:-1]).any(axis=1)

    left_id = np.cumsum(new_left) - 1       # per sorted bucket
    sub_id = np.cumsum(new_right) - 1
    n_left = int(left_id[-1]) + 1
    n_sub = int(sub_id[-1]) + 1
    sub_starts = np.flatnonzero(new_right)
    # left group of each subgroup / subgroup count per left group
    sub_left = left_id[sub_starts]
    subs_per_left = np.bincount(sub_left, minlength=n_left)

    group_skip = np.zeros(n_left, dtype=bool)
    group_skip[left_id[new_left]] = sl[new_left, 1] == 0  # left kind None

    sub_is_frag = sr[sub_starts, 0] == 0
    sub_only_frag = sub_is_frag & (subs_per_left[sub_left] == 1)
    sub_keep_best = (sub_only_frag | ~sub_is_frag) & ~group_skip[sub_left]
    sub_mark_all = sub_is_frag & (subs_per_left[sub_left] > 1) & ~group_skip[sub_left]

    # best bucket per subgroup: max score, first (stable order) wins
    score_sorted = bucket_score[go]
    max_sc = np.maximum.reduceat(score_sorted, sub_starts)
    pos = np.arange(len(go), dtype=np.int64)
    is_max = score_sorted == max_sc[sub_id]
    first_best = np.full(n_sub, len(go), dtype=np.int64)
    rows_max = np.flatnonzero(is_max)
    np.minimum.at(first_best, sub_id[rows_max], pos[rows_max])

    marked_sub = sub_keep_best | sub_mark_all
    primary_dup_sorted = marked_sub[sub_id]
    secondary_dup_sorted = primary_dup_sorted.copy()
    # unmark the best bucket of keep-best subgroups (primaries only)
    best_pos = first_best[np.flatnonzero(sub_keep_best)]
    primary_dup_sorted[best_pos] = False

    primary_dup = np.zeros(n_buckets, dtype=bool)
    secondary_dup = np.zeros(n_buckets, dtype=bool)
    primary_dup[go] = primary_dup_sorted
    secondary_dup[go] = secondary_dup_sorted

    # ----- back to rows ------------------------------------------------
    row_bucket = np.clip(bucket_of, 0, None)
    dup = np.where(
        mapped & primary,
        primary_dup[row_bucket],
        np.where(mapped, secondary_dup[row_bucket], False),
    )
    dup &= valid & (bucket_of >= 0)
    return dup


def apply_duplicate_flags(flags: np.ndarray, dup: np.ndarray) -> np.ndarray:
    return np.where(
        dup, flags | schema.FLAG_DUPLICATE, flags & ~schema.FLAG_DUPLICATE
    ).astype(np.int32)


def mark_duplicates(
    ds: AlignmentDataset, backend: str | None = None
) -> AlignmentDataset:
    """Single-batch duplicate marking.  ``backend`` follows the shared
    per-residue flag (:func:`adam_tpu.pipelines.bqsr.bqsr_backend`):
    ``device`` runs the [N, L] key/score reductions on the chip (the
    default when one is attached); the host twins otherwise."""
    from adam_tpu.pipelines.bqsr import bqsr_backend

    b = ds.batch.to_numpy()
    if b.n_rows == 0:
        return ds
    if bqsr_backend(backend) == "device":
        five, score = markdup_columns_device(ds.batch)
        s = row_summary(ds, b, five_prime=five, score=score)
    else:
        s = row_summary(ds, b)
    dup = resolve_duplicates(s)
    new_flags = apply_duplicate_flags(np.asarray(b.flags), dup)
    return ds.with_batch(b.replace(flags=new_flags))
