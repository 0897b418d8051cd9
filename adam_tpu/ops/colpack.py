"""On-device column packing — [N, L] matrices -> flat Arrow-layout buffers.

The streamed pipeline's pass C used to fetch each window's recalibrated
quals as a dense ``u8[N, L]`` matrix and re-walk it on the host into the
Arrow string layout (one flat byte buffer + offsets).  The device
already knows every row's true length, so the kernel here does the
compaction *before* the bytes cross the link: each row's in-read prefix
moves to its exclusive-cumsum offset, the window ships
``packed[:total]`` — the exact column payload, padding lanes never
cross d2h — and the host gets a buffer that IS the Arrow data buffer
(io/arrow_pack.py wraps it zero-copy).  Offsets never cross at all: the
host holds the same lengths and rebuilds them with one cumsum.

The compaction is a log-step shift network, not a scatter.  Every byte
of row ``r`` moves left by the row's deficit ``r * L - offset[r]``;
stage ``k`` moves the bytes whose deficit has bit ``k`` set by
``2**k``, so a window of ``N * L`` lanes packs in
``bit_length((N - 1) * L)`` stages, one loop of a shifted slice and two
selects each.  No sort, scatter or gather is left in the program (a u8
scatter makes the TPU compiler sort its whole index array first), and
the cost depends on the static grid alone, not on the length mix.
Base codes decode to ASCII with five selects for the same reason.

On trimmed/short-read libraries — adapter-trimmed short-insert runs,
small-RNA reads at a fraction of the instrument read length —
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
from jax import lax

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
    """Traceable pack: row prefixes ``mat[i, :lens[i]]`` concatenated in
    row order into a flat ``[size]`` buffer, zero after ``sum(lens)``.

    A monotone compaction by a log-step shift network: every lane of
    row ``r`` moves left by the row's deficit ``r * w - offset[r]``,
    which never decreases along the flat order.  Stage ``k`` moves each
    kept lane whose deficit has bit ``k`` set left by ``2**k``; taken in
    increasing ``k``, no two kept lanes ever meet and their order holds.
    The stages are one loop over the flat lanes, each a shifted slice
    and two selects: no sort, scatter or gather, and a cost set by the
    static grid alone, not by the length mix.

    ``size`` must be static and >= ``sum(lens)``; callers use the
    window's dense grid area (``g * gl``) so the jit cache sees no new
    shapes — the *fetch* is what shrinks (``packed[:total]``), not the
    device allocation, which aliases the matrix footprint it replaces.
    """
    n, w = mat.shape
    flat = n * w
    total = max(flat, size)
    bits = max((n - 1) * w, 0).bit_length()  # every deficit < 2**bits
    reach = (1 << bits) >> 1  # the longest shift
    assert total + reach < 2**31, "pack offsets are i32"
    lens = jnp.clip(lens.astype(jnp.int32), 0, w)
    deficit = jnp.arange(n, dtype=jnp.int32) * w - (jnp.cumsum(lens) - lens)
    in_row = jnp.arange(w, dtype=jnp.int32)[None, :] < lens[:, None]
    # key: the lane's deficit, -1 where no byte is kept; lanes past the
    # grid hold none, so a shift past the end reads empty lanes
    tail = total - flat + reach
    key = jnp.pad(jnp.where(in_row, deficit[:, None], -1).ravel(),
                  (0, tail), constant_values=-1)
    val = jnp.pad(jnp.where(in_row, mat, 0).astype(jnp.int32).ravel(),
                  (0, tail))

    def stage(k, kv):
        key, val = kv
        s = jnp.left_shift(1, k)
        key_in = lax.dynamic_slice(key, (s,), (total,))
        arrive = (key_in >= 0) & (key_in & s != 0)
        stay = (key[:total] >= 0) & (key[:total] & s == 0)
        return [
            lax.dynamic_update_slice(x, jnp.where(
                arrive, lax.dynamic_slice(x, (s,), (total,)),
                jnp.where(stay, x[:total], fill)), (0,))
            for x, fill in ((key, -1), (val, 0))
        ]

    _, val = lax.fori_loop(0, bits, stage, [key, val])
    return val[:size].astype(mat.dtype)


@partial(jax.jit, static_argnames=("size",))
def pack_rows_kernel(mat, lens, size: int):
    """Jit entry point over :func:`pack_rows_body` (standalone packing
    of an already-resident matrix; the apply path fuses the body into
    its own kernel instead — one dispatch, no intermediate)."""
    return pack_rows_body(mat, lens, size)


def pack_rows_np(mat: np.ndarray, lens: np.ndarray) -> np.ndarray:
    """Host twin of :func:`pack_rows_body` (exact total, no padding):
    one boolean mask-select in row-major order — concatenated row
    prefixes, bitwise the device pack's first ``sum(lens)`` bytes."""
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
    on device, decoding there costs five elementwise selects instead of
    a host LUT walk per part; codes past ``BASE_N`` decode as ``.``)."""
    lut = schema.BASE_DECODE_LUT
    out = jnp.full(bases.shape, lut[schema.BASE_PAD], jnp.uint8)
    for code in range(schema.BASE_PAD):
        out = jnp.where(bases == code, lut[code], out)
    return out


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

