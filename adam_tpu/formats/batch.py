"""Columnar read batches — the device-side data model.

The reference's unit of data is one Avro ``AlignmentRecord`` object per read
flowing through Spark RDDs.  Here the unit is a **batch**: a struct of
padded, masked arrays ``[N, Lmax]`` that lives in HBM and is the argument
to every kernel.  This is what makes ``vmap``/``shard_map`` work and keeps
the MXU fed.

Split of responsibilities:

* :class:`ReadBatch` — pure JAX pytree of arrays.  Safe to pass through
  ``jit``/``shard_map``; every transform is ``ReadBatch -> ReadBatch``.
* :class:`ReadSidecar` — host-only variable-length columns (read names,
  attribute strings, MD tags) kept out of the device path, carried
  alongside by the API layer (:mod:`adam_tpu.api`).

Field parity with the reference's AlignmentRecord (field list at
projections/AlignmentRecordField.scala:29-31): sequence/qual -> ``bases``/
``quals`` (integer-coded), the 12 boolean flag fields -> packed ``flags``,
contig/start/end/mapq/cigar/mate* -> same-named columns, recordGroup* ->
``read_group_idx`` into a :class:`RecordGroupDictionary`, readName/
attributes/mdTag/origQual -> sidecar.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from adam_tpu.formats import schema

Array = Any  # jnp.ndarray or np.ndarray


def grid_rows(n: int, minimum: int = 1024) -> int:
    """Device-friendly row count: the next power of two, floored at
    ``minimum``.

    Two reasons to quantize row counts before a device call: (1) the
    persistent compilation cache then sees a handful of shapes instead of
    one per input file, and (2) the TPU compiler's gather/scatter
    strategies have a pathological compile-time hump for mid-size
    irregular row counts (measured: ~50 s at N=98304 vs ~1.5 s at
    N=131072 for the same gather); power-of-two rows stay on the fast
    path.  Padding rows carry valid=False and are masked out by every
    kernel.
    """
    n = max(int(n), 1)
    g = max(minimum, 1 << (n - 1).bit_length())
    return g


def grid_cols(n: int, mult: int = 32) -> int:
    """Device-friendly lane count: next multiple of ``mult``.

    Aligned minor dims keep the compile-cache shape set small and the
    TPU's (8, 128) tiling whole."""
    return _round_up(max(int(n), 1), mult)


def grid_cigar_cols(width: int) -> int:
    """Cigar-op grid: multiples of 8 instead of :func:`grid_cols`'s 32.

    Op counts are small (typically < 16 on short-read libraries) while
    the [N, C] i32 ``cigar_lens`` matrix ships host->device with every
    pass-A markdup window — at the 32-floor, 3/4 of those h2d bytes
    were pure padding zeros.  Multiples of 8 stay sublane-aligned for
    the i32 lens (and trivially for the u8 ops) and keep the compile-
    cache shape set bounded; the streamed first-sight re-prewarm covers
    the extra gc values a long-cigar window can introduce."""
    return grid_cols(width, mult=8)


def pad_rows_np(arr, n: int, fill=0, cols: int | None = None):
    """Pad a numpy array's leading axis up to ``n`` rows (and, for 2-d
    arrays when ``cols`` is given, the second axis up to ``cols``) with
    ``fill``."""
    arr = np.asarray(arr)
    extra_rows = n - arr.shape[0]
    extra_cols = (cols - arr.shape[1]) if (cols is not None and arr.ndim > 1) else 0
    if extra_rows == 0 and extra_cols == 0:
        return arr
    pad_width = [(0, extra_rows), (0, extra_cols)] + [(0, 0)] * (arr.ndim - 2)
    return np.pad(arr, pad_width[: arr.ndim], constant_values=fill)


def _round_up(n: int, mult: int) -> int:
    return ((n + mult - 1) // mult) * mult


@jax.tree_util.register_dataclass
@dataclass(frozen=True)
class ReadBatch:
    """Struct-of-arrays batch of (up to) N reads, padded to [N, L] / [N, C].

    Padding rows have ``valid == False``; padding lanes within a read have
    ``bases == BASE_PAD`` and ``quals == QUAL_PAD``.
    """

    bases: Array          # u8[N, L]   base codes (schema.BASE_*)
    quals: Array          # u8[N, L]   phred values, QUAL_PAD in padding
    lengths: Array        # i32[N]     true read length
    flags: Array          # i32[N]     packed SAM flags
    contig_idx: Array     # i32[N]     index into SequenceDictionary, -1 unmapped
    start: Array          # i64[N]     0-based inclusive, -1 if unmapped
    end: Array            # i64[N]     0-based exclusive (start + ref span)
    mapq: Array           # i32[N]     255 = unavailable
    cigar_ops: Array      # u8[N, C]   schema.CIGAR_* codes, CIGAR_PAD pad
    cigar_lens: Array     # i32[N, C]
    cigar_n: Array        # i32[N]     number of real cigar ops
    mate_contig_idx: Array  # i32[N]   -1 if mate unmapped/absent
    mate_start: Array     # i64[N]
    tlen: Array           # i32[N]    template length (SAM TLEN)
    read_group_idx: Array  # i32[N]   index into RecordGroupDictionary, -1 none
    has_qual: Array       # bool[N]   false when qual was '*' (null in the reference)
    valid: Array          # bool[N]   row mask

    # ---------------------------------------------------------------- sizes
    @property
    def n_rows(self) -> int:
        return int(self.bases.shape[0])

    @property
    def lmax(self) -> int:
        return int(self.bases.shape[1])

    @property
    def cmax(self) -> int:
        return int(self.cigar_ops.shape[1])

    def n_valid(self) -> int:
        return int(np.asarray(self.valid).sum())

    # ------------------------------------------------------------ flag views
    def flag_set(self, bit: int) -> Array:
        return (self.flags & bit) != 0

    @property
    def is_mapped(self) -> Array:
        return (self.flags & schema.FLAG_UNMAPPED) == 0

    @property
    def is_primary(self) -> Array:
        return (self.flags & (schema.FLAG_SECONDARY | schema.FLAG_SUPPLEMENTARY)) == 0

    # ------------------------------------------------------------- reshaping
    def pad_rows(self, n: int) -> "ReadBatch":
        """Pad to exactly ``n`` rows (valid=False padding)."""
        cur = self.n_rows
        if cur == n:
            return self
        if cur > n:
            raise ValueError(f"cannot pad {cur} rows down to {n}")
        extra = n - cur

        def pad(x, fill):
            pad_width = [(0, extra)] + [(0, 0)] * (x.ndim - 1)
            return np.pad(np.asarray(x), pad_width, constant_values=fill)

        return ReadBatch(
            bases=pad(self.bases, schema.BASE_PAD),
            quals=pad(self.quals, schema.QUAL_PAD),
            lengths=pad(self.lengths, 0),
            flags=pad(self.flags, schema.FLAG_UNMAPPED),
            contig_idx=pad(self.contig_idx, -1),
            start=pad(self.start, -1),
            end=pad(self.end, -1),
            mapq=pad(self.mapq, 255),
            cigar_ops=pad(self.cigar_ops, schema.CIGAR_PAD),
            cigar_lens=pad(self.cigar_lens, 0),
            cigar_n=pad(self.cigar_n, 0),
            mate_contig_idx=pad(self.mate_contig_idx, -1),
            mate_start=pad(self.mate_start, -1),
            tlen=pad(self.tlen, 0),
            read_group_idx=pad(self.read_group_idx, -1),
            has_qual=pad(self.has_qual, False),
            valid=pad(self.valid, False),
        )

    def take(self, idx: Array) -> "ReadBatch":
        """Row gather preserving residency: numpy batches gather on the
        host, device batches on the device.  (Coercing to jnp here used
        to ship every host window to the chip and back.)"""
        return jax.tree.map(lambda x: x[idx], self)

    def replace(self, **kw) -> "ReadBatch":
        return dataclasses.replace(self, **kw)

    def to_numpy(self) -> "ReadBatch":
        return jax.tree.map(np.asarray, self)

    def to_device(self) -> "ReadBatch":
        return jax.tree.map(jnp.asarray, self)

    # ----------------------------------------------------------- constructors
    @staticmethod
    def empty(n: int = 0, lmax: int = 0, cmax: int = 0) -> "ReadBatch":
        return ReadBatch(
            bases=np.full((n, lmax), schema.BASE_PAD, np.uint8),
            quals=np.full((n, lmax), schema.QUAL_PAD, np.uint8),
            lengths=np.zeros(n, np.int32),
            flags=np.full(n, schema.FLAG_UNMAPPED, np.int32),
            contig_idx=np.full(n, -1, np.int32),
            start=np.full(n, -1, np.int64),
            end=np.full(n, -1, np.int64),
            mapq=np.full(n, 255, np.int32),
            cigar_ops=np.full((n, cmax), schema.CIGAR_PAD, np.uint8),
            cigar_lens=np.zeros((n, cmax), np.int32),
            cigar_n=np.zeros(n, np.int32),
            mate_contig_idx=np.full(n, -1, np.int32),
            mate_start=np.full(n, -1, np.int64),
            tlen=np.zeros(n, np.int32),
            read_group_idx=np.full(n, -1, np.int32),
            has_qual=np.zeros(n, bool),
            valid=np.zeros(n, bool),
        )

    @staticmethod
    def concat(batches: Sequence["ReadBatch"]) -> "ReadBatch":
        """Concatenate along rows, widening L/C to the max."""
        batches = [b for b in batches if b.n_rows]
        if not batches:
            return ReadBatch.empty()
        lmax = max(b.lmax for b in batches)
        cmax = max(b.cmax for b in batches)
        batches = [b.widen(lmax, cmax).to_numpy() for b in batches]
        return jax.tree.map(lambda *xs: np.concatenate(xs, axis=0), *batches)

    def widen(self, lmax: int, cmax: int) -> "ReadBatch":
        """Grow the per-read padding lanes to lmax/cmax."""
        if lmax == self.lmax and cmax == self.cmax:
            return self

        def padlane(x, width, fill):
            x = np.asarray(x)
            if x.shape[1] == width:
                return x
            return np.pad(x, [(0, 0), (0, width - x.shape[1])], constant_values=fill)

        return self.replace(
            bases=padlane(self.bases, lmax, schema.BASE_PAD),
            quals=padlane(self.quals, lmax, schema.QUAL_PAD),
            cigar_ops=padlane(self.cigar_ops, cmax, schema.CIGAR_PAD),
            cigar_lens=padlane(self.cigar_lens, cmax, 0),
        )


@dataclass
class ReadSidecar:
    """Host-side variable-length columns, parallel to ReadBatch rows.

    String fields are stored columnar (:class:`StringColumn`: one flat
    byte buffer + offsets, Arrow layout) so whole-dataset operations stay
    vectorized; plain lists are accepted anywhere and normalized on
    construction.  Element access (``side.md[i]``) returns str/None either
    way.
    """

    names: Any = field(default_factory=list)       # read names
    attrs: Any = field(default_factory=list)       # raw SAM tag strings ("NM:i:0\tAS:i:75")
    md: Any = field(default_factory=list)          # MD tag string or None
    orig_quals: Any = field(default_factory=list)  # OQ or None
    # basesTrimmedFromStart/End bookkeeping (AlignmentRecord fields set by
    # TrimReads.trimRead, rdd/read/correction/TrimReads.scala:363-368)
    trimmed_from_start: Any = None
    trimmed_from_end: Any = None

    def __post_init__(self):
        from adam_tpu.formats.strings import StringColumn

        self.names = StringColumn.of(self.names)
        self.attrs = StringColumn.of(self.attrs)
        self.md = StringColumn.of(self.md)
        self.orig_quals = StringColumn.of(self.orig_quals)
        n = len(self.names)
        if self.trimmed_from_start is None:
            self.trimmed_from_start = np.zeros(n, np.int32)
        else:
            self.trimmed_from_start = np.asarray(
                self.trimmed_from_start, np.int32
            )
        if self.trimmed_from_end is None:
            self.trimmed_from_end = np.zeros(n, np.int32)
        else:
            self.trimmed_from_end = np.asarray(self.trimmed_from_end, np.int32)

    def take(self, idx) -> "ReadSidecar":
        idx = np.asarray(idx)
        return ReadSidecar(
            names=self.names.take(idx),
            attrs=self.attrs.take(idx),
            md=self.md.take(idx),
            orig_quals=self.orig_quals.take(idx),
            trimmed_from_start=self.trimmed_from_start[idx],
            trimmed_from_end=self.trimmed_from_end[idx],
        )

    @staticmethod
    def concat(sides: Sequence["ReadSidecar"]) -> "ReadSidecar":
        from adam_tpu.formats.strings import StringColumn

        if not sides:
            return ReadSidecar()
        return ReadSidecar(
            names=StringColumn.concat([s.names for s in sides]),
            attrs=StringColumn.concat([s.attrs for s in sides]),
            md=StringColumn.concat([s.md for s in sides]),
            orig_quals=StringColumn.concat([s.orig_quals for s in sides]),
            trimmed_from_start=np.concatenate(
                [np.asarray(s.trimmed_from_start, np.int32) for s in sides]
            ),
            trimmed_from_end=np.concatenate(
                [np.asarray(s.trimmed_from_end, np.int32) for s in sides]
            ),
        )

    def __len__(self) -> int:
        return len(self.names)


def pack_reads(
    records: Sequence[dict],
    lmax: int | None = None,
    cmax: int | None = None,
    round_rows_to: int = 1,
) -> tuple[ReadBatch, ReadSidecar]:
    """Build a (ReadBatch, ReadSidecar) from parsed per-read dicts.

    Each record dict carries: name, flags, contig_idx, start (0-based, -1
    unmapped), mapq, cigar (string), seq (string), qual (phred string or
    '*'), mate_contig_idx, mate_start, tlen, read_group_idx, attrs (raw tag
    string), md (or None).
    """
    n = len(records)
    if n == 0:
        return ReadBatch.empty(), ReadSidecar()
    if lmax is None:
        lmax = max((len(r["seq"]) if r["seq"] not in ("*", None) else 0) for r in records)
        lmax = max(lmax, 1)
    if cmax is None:
        cmax = 1
        for r in records:
            c = r.get("cigar") or "*"
            cmax = max(cmax, sum(1 for ch in c if not ch.isdigit()))
    nrows = _round_up(n, round_rows_to)

    b = ReadBatch.empty(nrows, lmax, cmax)
    b = jax.tree.map(np.array, b)  # writable copies
    s_names, s_attrs, s_md, s_oq, s_tfs, s_tfe = [], [], [], [], [], []

    for i, r in enumerate(records):
        seq = r["seq"] if r["seq"] not in ("*", None) else ""
        qual = r.get("qual")
        L = len(seq)
        if L:
            b.bases[i, :L] = schema.encode_bases(seq)
        if qual and qual != "*":
            b.quals[i, : len(qual)] = schema.encode_quals(qual)
            b.has_qual[i] = True
        elif L:
            b.quals[i, :L] = 0
        b.lengths[i] = L
        b.flags[i] = r["flags"]
        b.contig_idx[i] = r.get("contig_idx", -1)
        start = r.get("start", -1)
        b.start[i] = start
        b.mapq[i] = r.get("mapq", 255)
        cig = r.get("cigar") or "*"
        ops, lens, ncig = schema.encode_cigar(cig, cmax)
        b.cigar_ops[i] = ops
        b.cigar_lens[i] = lens
        b.cigar_n[i] = ncig
        _, rlen = schema.cigar_str_stats(cig) if cig != "*" else (0, 0)
        # end = start for mapped reads whose CIGAR consumes no reference
        # (e.g. fully soft-clipped); -1 is reserved for unplaced reads.
        b.end[i] = start + rlen if start >= 0 else -1
        b.mate_contig_idx[i] = r.get("mate_contig_idx", -1)
        b.mate_start[i] = r.get("mate_start", -1)
        b.tlen[i] = r.get("tlen", 0)
        b.read_group_idx[i] = r.get("read_group_idx", -1)
        b.valid[i] = True

        s_names.append(r.get("name", ""))
        s_attrs.append(r.get("attrs", ""))
        s_md.append(r.get("md"))
        s_oq.append(r.get("orig_qual"))
        s_tfs.append(r.get("trimmed_from_start", 0))
        s_tfe.append(r.get("trimmed_from_end", 0))

    # padding rows keep empty sidecar slots so columns stay row-parallel
    pad = nrows - n
    side = ReadSidecar(
        names=s_names + [""] * pad,
        attrs=s_attrs + [""] * pad,
        md=s_md + [None] * pad,
        orig_quals=s_oq + [None] * pad,
        trimmed_from_start=np.asarray(s_tfs + [0] * pad, np.int32),
        trimmed_from_end=np.asarray(s_tfe + [0] * pad, np.int32),
    )
    return b, side
