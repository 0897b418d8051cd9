"""Known-variant tables.

``SnpTable`` (models/SnpTable.scala:28-97): per-contig sets of known SNP
positions, built empty, from a sites-only VCF-like file (contig, 1-based
pos, id, ref — one masked site per ref base), or from variants.
``IndelTable`` (models/IndelTable.scala:26-90): known indels for the
knowns-based realignment consensus model.

Device form: positions are kept as sorted i64 arrays per contig (the
broadcast role of the Spark-side table).  Batch masking reads a dense u8
map of the sites inside the batch's own reference span, one gather per
residue; a batch whose span is too wide for that map (unsorted input, a
whole chromosome) falls back to a ``searchsorted`` membership test.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import NamedTuple

import numpy as np

from adam_tpu.models.positions import ReferenceRegion

# rows per block of the map gather: a block's i64 indices (8192 x L)
# stay in cache between the add, the clip and the gather (262,144 x 100
# positions on a TPU v5e host: 0.08 s, against 0.34 s in one pass)
_GATHER_ROWS = 8192


class SnpMask(NamedTuple):
    """A batch's known-SNP mask and the route that computed it."""

    mask: np.ndarray  # bool[N, L]
    hit_rows: int  # rows whose reference span holds >= 1 known site
    fallback_rows: int  # rows masked by the searchsorted route


class SnpTable:
    def __init__(self, table: dict[str, np.ndarray] | None = None):
        # contig name -> sorted unique i64 positions
        self.table = {
            k: np.unique(np.asarray(v, dtype=np.int64))
            for k, v in (table or {}).items()
        }

    @staticmethod
    def from_file(path: str) -> "SnpTable":
        """Sites-only VCF-ish file: TAB columns (contig, 1-based pos, id,
        ref, ...); every base of ref masks one site (SnpTable.scala:66-90)."""
        with open(path) as fh:
            return SnpTable.from_lines(fh)

    @staticmethod
    def from_lines(lines) -> "SnpTable":
        table: dict[str, list[int]] = {}
        for line in lines:
            if not line.strip() or line.startswith("#"):
                continue
            parts = line.rstrip("\n").split("\t")
            contig, pos, ref = parts[0], int(parts[1]) - 1, parts[3]
            assert pos >= 0 and ref
            for i in range(len(ref)):
                table.setdefault(contig, []).append(pos + i)
        return SnpTable(table)

    @staticmethod
    def from_variants(variants) -> "SnpTable":
        """From (contig, 0-based pos) pairs (the loadVariants path)."""
        table: dict[str, list[int]] = {}
        for contig, pos in variants:
            table.setdefault(contig, []).append(pos)
        return SnpTable(table)

    def site_keys(self, contig_names: list[str]) -> np.ndarray:
        """Sorted composite ``contig_index << 40 | position`` site keys
        for the native observe kernel's in-walk masking."""
        keys = []
        for ci, name in enumerate(contig_names):
            arr = self.table.get(name)
            if arr is not None and len(arr):
                keys.append((np.int64(ci) << 40) | arr.astype(np.int64))
        if not keys:
            return np.zeros(0, np.int64)
        return np.sort(np.concatenate(keys))

    def contains(self, contig: str, pos: int) -> bool:
        arr = self.table.get(contig)
        if arr is None or not len(arr):
            return False
        i = np.searchsorted(arr, pos)
        return i < len(arr) and arr[i] == pos

    def mask_positions(
        self, contig_names: list[str], contig_idx, positions, start=None
    ) -> np.ndarray:
        """Vectorized membership test -> bool mask of known-SNP sites.

        ``contig_idx`` is per-row i32[N] (one contig per read);
        ``positions`` is i64[N, L] per-base reference positions (< 0 =
        no position -> False); ``start`` (optional, i64[N]) is a lower
        bound of each row's non-negative positions, the alignment start,
        which saves a pass over ``positions``.
        """
        return self.mask_routed(contig_names, contig_idx, positions, start).mask

    def mask_routed(
        self, contig_names: list[str], contig_idx, positions, start=None
    ) -> SnpMask:
        """:meth:`mask_positions` with the route it took.

        Each row's reference span is [lo, hi]: ``start`` (or the row's
        smallest non-negative position) to its largest position.  The
        sites inside the spans of each contig's rows are written into a
        zeroed u8 map, the contigs' maps laid end to end behind zero
        guard bytes, and every residue reads its map byte at ``position
        + base_c - lo_c``, clipped to its contig's guards (so negative
        positions and rows with no contig in the table read a zero).
        When the maps would outgrow the positions' own N x L bytes, the
        rows whose span holds a site take a per-contig ``searchsorted``.
        """
        contig_idx = np.asarray(contig_idx)
        positions = np.asarray(positions)
        n, L = positions.shape
        out = np.zeros((n, L), dtype=bool)
        if not n or not L:
            return SnpMask(out, 0, 0)
        hi = positions.max(axis=1)
        if start is None:
            lo = np.where(
                positions >= 0, positions, np.iinfo(np.int64).max
            ).min(axis=1)
        else:
            lo = np.maximum(np.asarray(start, dtype=np.int64), 0)
        live = (hi >= 0) & (contig_idx >= 0) & (contig_idx < len(contig_names))
        present = np.flatnonzero(
            np.bincount(contig_idx[live], minlength=len(contig_names))
        )
        # per contig in the table: its rows, sites, span and map base
        hits = np.zeros(n, np.int64)
        groups = []
        base = 1  # map byte 0 is the leading guard
        for ci in present:
            arr = self.table.get(contig_names[ci])
            if arr is None or not len(arr):
                continue
            rows = np.flatnonzero(live & (contig_idx == ci))
            hits[rows] = np.searchsorted(arr, hi[rows], "right") - (
                np.searchsorted(arr, lo[rows])
            )
            lo_c, hi_c = int(lo[rows].min()), int(hi[rows].max())
            groups.append((rows, arr, lo_c, hi_c, base))
            base += hi_c - lo_c + 2  # the span and its trailing guard
        hit_rows = int(np.count_nonzero(hits))

        if base > n * L:
            fallback_rows = 0
            for rows, arr, *_ in groups:
                fallback_rows += len(rows)
                rows = rows[hits[rows] > 0]  # the others mask nothing
                pos = positions[rows]
                idx = np.minimum(np.searchsorted(arr, pos), len(arr) - 1)
                out[rows] = (arr[idx] == pos) & (pos >= 0)
            return SnpMask(out, hit_rows, fallback_rows)

        site_map = np.zeros(base, np.uint8)
        off = np.zeros(n, np.int64)
        guard_lo = np.zeros(n, np.int64)
        guard_hi = np.zeros(n, np.int64)
        for rows, arr, lo_c, hi_c, b0 in groups:
            sites = arr[np.searchsorted(arr, lo_c):
                        np.searchsorted(arr, hi_c, "right")]
            site_map[b0 + sites - lo_c] = 1
            off[rows] = b0 - lo_c
            guard_lo[rows] = b0 - 1
            guard_hi[rows] = b0 + hi_c - lo_c + 1
        for r in range(0, n, _GATHER_ROWS):
            k = slice(r, r + _GATHER_ROWS)
            idx = positions[k] + off[k, None]
            np.clip(idx, guard_lo[k, None], guard_hi[k, None], out=idx)
            out[k] = site_map.take(idx).view(bool)
        return SnpMask(out, hit_rows, 0)

    def __len__(self) -> int:
        return sum(len(v) for v in self.table.values())


@dataclass(frozen=True)
class IndelRecord:
    region: ReferenceRegion
    consensus: str  # inserted bases, or "" for deletion


class IndelTable:
    """Known indels per contig (IndelTable.scala:26-66)."""

    def __init__(self, table: dict[str, list[IndelRecord]] | None = None):
        self.table = dict(table or {})

    @staticmethod
    def from_variants(variants) -> "IndelTable":
        """From (contig, 0-based pos, ref, alt) tuples: insertion when
        len(ref)==1<len(alt) — consensus is alt minus anchor base at the
        anchor position; deletion when len(alt)==1<len(ref) — region spans
        the deleted bases (IndelTable.scala:43-64)."""
        table: dict[str, list[IndelRecord]] = {}
        for contig, pos, ref, alt in variants:
            if len(ref) == 1 and len(alt) > 1:
                rec = IndelRecord(
                    ReferenceRegion(contig, pos, pos + 1), alt[1:]
                )
            elif len(alt) == 1 and len(ref) > 1:
                rec = IndelRecord(
                    ReferenceRegion(contig, pos + 1, pos + len(ref)), ""
                )
            else:
                continue
            table.setdefault(contig, []).append(rec)
        return IndelTable(table)

    def get_indels_in_region(self, region: ReferenceRegion) -> list[IndelRecord]:
        return [
            r
            for r in self.table.get(region.referenceName, [])
            if r.region.overlaps(region)
        ]
