"""On-device column packing — [N, L] matrices -> flat Arrow-layout buffers.

The streamed pipeline's pass C used to fetch each window's recalibrated
quals as a dense ``u8[N, L]`` matrix and re-walk it on the host into the
Arrow string layout (one flat byte buffer + offsets).  The device
already knows every row's true length, so the kernel here does the
compaction *before* the bytes cross the link: scatter each row's
in-read prefix at its exclusive-cumsum offset, ship ``packed[:total]``
— the exact column payload, padding lanes never cross d2h — and hand
the host a buffer that IS the Arrow data buffer (io/arrow_pack.py wraps
it zero-copy).  Offsets never cross at all: the host holds the same
lengths and rebuilds them with one cumsum.

The same shrink-the-d2h move as PR 8's barrier-2 mesh psum, applied to
the pass-C apply fetch (the ROADMAP "kill the apply/encode/write tail"
item): on trimmed/short-read libraries — adapter-trimmed short-insert
runs, small-RNA reads at a fraction of the instrument read length —
``sum(lengths)`` is several times smaller than ``N*L``, and the ledger's
pass-C ``device.d2h.bytes`` entry shrinks by the same factor.

``pack_rows_body`` is a plain traceable function so mesh ``shard_map``
bodies can fuse it after the apply gather (each shard packs its own row
block; the host concatenates shard payloads in shard order, which is
row order).  ``pack_rows_np`` is the bit-parity host twin used by the
fallback paths and the differential tests.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from adam_tpu.formats import schema


def pack_lengths(lengths, valid, has_qual=None) -> np.ndarray:
    """Per-row packed byte counts for a qual/base column: the true read
    length for rows that carry the column, 0 for padding/invalid rows
    (and, when ``has_qual`` is given, for rows whose qual was ``'*'`` —
    those rows are NULL in the Arrow column and contribute no bytes)."""
    lens = np.where(np.asarray(valid), np.asarray(lengths), 0)
    if has_qual is not None:
        lens = np.where(np.asarray(has_qual), lens, 0)
    return lens.astype(np.int64)


def pack_rows_body(mat, lens, size: int):
    """Traceable pack: scatter row prefixes ``mat[i, :lens[i]]`` at
    exclusive-cumsum offsets into a flat ``[size]`` buffer.

    ``size`` must be static and >= ``sum(lens)``; callers use the
    window's dense grid area (``g * gl``) so the jit cache sees no new
    shapes — the *fetch* is what shrinks (``packed[:total]``), not the
    device allocation, which aliases the matrix footprint it replaces.
    Padding positions scatter to index ``size`` and drop.
    """
    n, w = mat.shape
    lens = lens.astype(jnp.int64)
    offsets = jnp.cumsum(lens) - lens  # exclusive row starts
    col = jnp.arange(w, dtype=jnp.int64)[None, :]
    in_row = col < lens[:, None]
    idx = jnp.where(in_row, offsets[:, None] + col, size)
    return (
        jnp.zeros(size, mat.dtype)
        .at[idx.ravel()]
        .set(mat.ravel(), mode="drop")
    )


@partial(jax.jit, static_argnames=("size",))
def pack_rows_kernel(mat, lens, size: int):
    """Jit entry point over :func:`pack_rows_body` (standalone packing
    of an already-resident matrix; the apply path fuses the body into
    its own kernel instead — one dispatch, no intermediate)."""
    return pack_rows_body(mat, lens, size)


def pack_rows_np(mat: np.ndarray, lens: np.ndarray) -> np.ndarray:
    """Host twin of :func:`pack_rows_body` (exact total, no padding):
    one boolean mask-select in row-major order — concatenated row
    prefixes, bitwise the device scatter's first ``sum(lens)`` bytes."""
    mat = np.ascontiguousarray(mat)
    lens = np.asarray(lens, np.int64)
    n, w = mat.shape if mat.ndim == 2 else (len(lens), 0)
    if n == 0 or w == 0:
        return np.zeros(0, mat.dtype)
    mask = np.arange(w)[None, :] < lens[:, None]
    return mat[mask]


def sanger_body(quals):
    """Traceable SANGER (phred+33) encode of a qual matrix — the device
    twin of ``schema.QUAL_SANGER_LUT256`` (min(q, 93) + 33), so packed
    qual buffers come home already ASCII, ready to BE the Arrow column
    data."""
    return (
        jnp.minimum(quals.astype(jnp.int32), 93) + schema.SANGER_OFFSET
    ).astype(jnp.uint8)


def base_decode_body(bases):
    """Traceable base decode of a code matrix — the device twin of
    ``schema.BASE_DECODE_LUT256`` (code -> ACGTN. ASCII), so packed
    base buffers come home ready to BE the Arrow ``sequence`` column
    data (the bases half of the packed tail: with the window resident
    on device, decoding there costs one tiny gather instead of a host
    LUT walk per part)."""
    return jnp.asarray(schema.BASE_DECODE_LUT256)[bases.astype(jnp.uint8)]


def pack_mask_bits(mask: np.ndarray) -> np.ndarray:
    """Bit-pack a host boolean [N, L] mask along its lane axis ->
    u8[N, ceil(L/8)] (``np.packbits`` big-endian layout).

    The resident-window observe dispatch ships its per-pass masks
    (residue_ok / is_mismatch — the only per-residue inputs that are
    genuinely host-derived, from the MD-tag walk) packed 8x, so the
    observe pass's h2d ledger entry stays ~0 next to the one ingest
    placement.  :func:`unpack_mask_body` is the device-side inverse."""
    return np.packbits(np.asarray(mask, bool), axis=1)


def unpack_mask_body(packed, n_cols: int):
    """Traceable inverse of :func:`pack_mask_bits`: u8[N, ceil(L/8)] ->
    bool[N, n_cols] (``n_cols`` static; trailing pad bits drop)."""
    shifts = (7 - jnp.arange(8, dtype=jnp.uint8))[None, None, :]
    bits = (packed[:, :, None] >> shifts) & jnp.uint8(1)
    n = packed.shape[0]
    return bits.reshape(n, -1)[:, :n_cols].astype(bool)


def fetch_grid(nbytes: int, floor: int = 4096) -> int:
    """Quantize a packed-payload byte count up to a coarse fetch
    bucket: the next multiple of 1/16th of its power-of-two scale
    (over-fetch < 6.25%), floored at 4 KiB.

    The d2h fetch is a device-side slice, and every distinct slice
    size is a distinct XLA program — per-window exact sizes would
    compile once per window (the same mid-run-compile trap the row
    grid quantization in ``formats/batch.grid_rows`` exists to avoid).
    Bucketing collapses a run's slice sizes to a handful of shapes;
    the host trims the tail bytes after the fetch."""
    n = max(int(nbytes), 1)
    q = max(floor, 1 << max(0, n.bit_length() - 4))
    return -(-n // q) * q


def packed_columns_enabled(default: bool = True) -> bool:
    """Resolve the ``ADAM_TPU_PACKED_COLS`` toggle for the pass-C
    packed-column fetch: ``auto``/unset -> ``default`` (on wherever the
    device apply runs), ``1/on/true`` and ``0/off/false`` force; a typo
    warns and keeps the default (``utils/retry.env_toggle``, the shared
    tuning-var contract)."""
    from adam_tpu.utils.retry import env_toggle

    return env_toggle("ADAM_TPU_PACKED_COLS", default)
