"""Distributed (multi-chip) kernels via shard_map + XLA collectives.

Spark-primitive -> collective mapping (SURVEY.md §2.5/§2.6):

* driver ``aggregate`` (flagstat, BQSR observation table, sequence
  dictionaries) -> ``psum`` of fixed-shape metric structs / histograms;
* ``reduceByKey`` over k-mers -> hash-sharded ``all_to_all`` exchange,
  then a local sort/run-length count of each shard's key slice;
* sort ``sortByKey`` -> splitter-based ``all_to_all`` redistribution +
  local sort;
* flanking/halo exchange between genome-adjacent fragments
  (FlankReferenceFragments.scala:26-70) -> ``ppermute`` with the
  neighbor shard.

Everything here runs under ``shard_map`` over a 1-D mesh, so the same
code drives 8 virtual CPU devices in tests, one real TPU chip, or a
multi-host pod (collectives ride ICI within a slice, DCN across hosts).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from adam_tpu.formats.batch import ReadBatch
from adam_tpu.ops import flagstat as fs
from adam_tpu.ops import kmer as kmer_ops
from adam_tpu.parallel.mesh import SHARD_AXIS, genome_mesh
from adam_tpu.utils.transfer import device_fetch


def _row_specs(batch: ReadBatch):
    return jax.tree.map(lambda _: P(SHARD_AXIS), batch)


def pad_batch_for_mesh(batch: ReadBatch, n_shards: int) -> ReadBatch:
    """Pad rows so the leading axis divides evenly across shards."""
    n = batch.n_rows
    target = -(-max(n, 1) // n_shards) * n_shards
    return batch.pad_rows(target)


# --------------------------------------------------------------------------
# psum aggregations
# --------------------------------------------------------------------------
def distributed_flagstat(batch: ReadBatch, mesh=None):
    """flagstat over a row-sharded batch; cross-chip combine is one psum
    of the metrics pytree (the reference's tree-aggregate to the driver).
    """
    mesh = mesh or genome_mesh()
    batch = pad_batch_for_mesh(batch, mesh.devices.size).to_device()

    out_struct = jax.eval_shape(fs.flagstat_device.__wrapped__, batch)

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(_row_specs(batch),),
        out_specs=jax.tree.map(lambda _: P(), out_struct),
        check_vma=False,
    )
    def run(local):
        failed, passed = fs.flagstat_device.__wrapped__(local)
        return jax.tree.map(lambda x: jax.lax.psum(x, SHARD_AXIS), (failed, passed))

    failed, passed = run(batch)
    return failed.to_ints(), passed.to_ints()


def distributed_observe(batch: ReadBatch, residue_ok, is_mismatch, read_ok,
                        n_rg: int, mesh=None):
    """BQSR observation histograms with cross-chip psum combine."""
    from adam_tpu.pipelines.bqsr import observe_kernel

    mesh = mesh or genome_mesh()
    n_shards = mesh.devices.size
    batch = pad_batch_for_mesh(batch, n_shards)
    lmax = batch.lmax

    def pad_rows(x):
        return np.pad(np.asarray(x), [(0, batch.n_rows - x.shape[0])] + [(0, 0)] * (x.ndim - 1))

    residue_ok = pad_rows(residue_ok)
    is_mismatch = pad_rows(is_mismatch)
    read_ok = pad_rows(read_ok)
    b = batch.to_device()

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(_row_specs(b), P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS)),
        out_specs=(P(), P()),
        check_vma=False,
    )
    def run(local, res_ok, is_mm, rd_ok):
        total, mism = observe_kernel.__wrapped__(
            local.bases, local.quals, local.lengths, local.flags,
            local.read_group_idx, res_ok, is_mm, rd_ok, n_rg, lmax,
        )
        return (
            jax.lax.psum(total, SHARD_AXIS),
            jax.lax.psum(mism, SHARD_AXIS),
        )

    return run(b, jnp.asarray(residue_ok), jnp.asarray(is_mismatch),
               jnp.asarray(read_ok))


# --------------------------------------------------------------------------
# fixed-capacity all_to_all routing, shared by k-mer count and sort
# --------------------------------------------------------------------------
def _route_all_to_all(values, dest, n_dev: int, pad, cap: int | None = None):
    """Send each value to its destination shard; returns (received,
    n_dropped) where ``received`` is the flat array of values landing on
    this shard (padded with ``pad``).

    ``cap`` bounds the per-destination send buffer: memory is
    O(n_dev * cap) per shard instead of the worst-case O(n_dev * m).
    Values beyond a destination's capacity are dropped and *counted* —
    callers run with a slack-factor capacity and fall back to the exact
    worst-case (cap = m) on the rare overflow (psum'd count > 0), so
    results are always exact.
    """
    m = values.shape[0]
    if cap is None:
        cap = m
    order = jnp.argsort(dest)
    vals_sorted = values[order]
    dest_sorted = dest[order]
    slot = (
        jnp.arange(m)
        - jnp.searchsorted(dest_sorted, jnp.arange(n_dev))[dest_sorted]
    )
    fits = slot < cap
    # overflowing values scatter into a trash slot past the real buffer
    flat = jnp.full(n_dev * cap + 1, pad, dtype=values.dtype)
    idx = jnp.where(fits, dest_sorted * cap + slot, n_dev * cap)
    flat = flat.at[idx].set(vals_sorted)
    buf = flat[: n_dev * cap].reshape(n_dev, cap)
    dropped = jax.lax.psum(jnp.sum(~fits), SHARD_AXIS)
    return jax.lax.all_to_all(buf, SHARD_AXIS, 0, 0).reshape(-1), dropped


def _mix_hash(keys):
    """Bit-mix i64 keys before modular sharding — the raw 3-bit-per-base
    packing puts only codes 0..4 in the low bits, which would starve most
    shards of a power-of-two mesh."""
    h = keys * jnp.int64(-7046029254386353131)  # 0x9E3779B97F4A7C15 as i64
    return (h >> 32) & jnp.int64(0x7FFFFFFF)


# --------------------------------------------------------------------------
# k-mer counting with hash-sharded all_to_all
# --------------------------------------------------------------------------
@partial(jax.jit, static_argnames=("k", "mesh", "cap"))
def _distributed_kmers_jit(bases, lengths, valid, k: int, mesh, cap=None):
    n_dev = mesh.devices.size

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS)),
        out_specs=(P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS), P()),
        check_vma=False,
    )
    def run(b, l, v):
        packed, win_valid = kmer_ops.extract_kmers(b, l, v, k)
        keys = jnp.where(win_valid, packed, jnp.int64(-1)).ravel()
        dest = jnp.where(keys >= 0, _mix_hash(keys) % n_dev, jnp.int64(0))
        mine, dropped = _route_all_to_all(keys, dest, n_dev, jnp.int64(-1), cap)
        s = jnp.sort(mine)
        is_new = jnp.concatenate([jnp.array([True]), s[1:] != s[:-1]])
        is_head = is_new & (s >= 0)
        seg = jnp.cumsum(is_new) - 1
        counts = jax.ops.segment_sum(
            (s >= 0).astype(jnp.int32), seg, num_segments=s.shape[0]
        )
        return s[None], counts[seg][None], is_head[None], dropped

    return run(bases, lengths, valid)


def distributed_count_kmers(batch: ReadBatch, k: int, mesh=None) -> dict[str, int]:
    """Exact global k-mer counts over a row-sharded batch.

    Local extraction -> hash-partitioned all_to_all so each device owns a
    disjoint key slice -> local sort/unique; host merges the per-device
    unique lists (no overlap by construction).
    """
    if batch.n_rows == 0:
        return {}
    mesh = mesh or genome_mesh()
    n_dev = mesh.devices.size
    batch = pad_batch_for_mesh(batch, n_dev).to_device()
    # capacity-bounded routing: 4x-uniform slack, exact-worst-case retry
    m = (batch.n_rows // n_dev) * (batch.lmax - k + 1)
    cap = min(m, 4 * m // n_dev + 64)
    s, counts, heads, dropped = _distributed_kmers_jit(
        batch.bases, batch.lengths, batch.valid, k, mesh, cap
    )
    if int(device_fetch(dropped)) > 0:  # rare: pathological key skew
        s, counts, heads, dropped = _distributed_kmers_jit(
            batch.bases, batch.lengths, batch.valid, k, mesh, m
        )
    s, counts, heads = (
        device_fetch(s), device_fetch(counts), device_fetch(heads)
    )
    out: dict[str, int] = {}
    for d in range(s.shape[0]):
        keys = s[d][heads[d]]
        vals = counts[d][heads[d]]
        for key, v in zip(keys, vals):
            out[kmer_ops.unpack_kmer(int(key), k)] = int(v)
    return out


# --------------------------------------------------------------------------
# distributed sort (splitter-based all_to_all)
# --------------------------------------------------------------------------
@partial(jax.jit, static_argnames=("mesh", "cap"))
def _distributed_sort_jit(keys, mesh, cap=None):
    n_dev = mesh.devices.size
    PAD = jnp.iinfo(jnp.int64).max

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(SHARD_AXIS),),
        out_specs=(P(SHARD_AXIS), P()),
        check_vma=False,
    )
    def run(local):
        local = local.ravel()
        # gather only n_dev local quantiles per shard (n_dev^2 values
        # total), not the full key array — splitter quality is the same
        # and the per-chip all_gather stays O(n_dev^2) instead of O(N)
        local_sorted = jnp.sort(local)
        qidx = (jnp.arange(n_dev) * local.shape[0]) // n_dev
        samples = jax.lax.all_gather(local_sorted[qidx], SHARD_AXIS).ravel()
        samples = jnp.sort(samples)
        # n_dev-1 splitters at even quantiles
        idx = (jnp.arange(1, n_dev) * samples.shape[0]) // n_dev
        splitters = samples[idx]
        dest = jnp.searchsorted(splitters, local, side="right")
        recv, dropped = _route_all_to_all(local, dest, n_dev, PAD, cap)
        return jnp.sort(recv)[None], dropped

    return run(keys)


def distributed_sort_keys(keys, mesh):
    """Globally sort an i64 key array sharded across the mesh.

    Sample-splitter strategy: all_gather a per-shard sample, derive
    n_dev-1 splitters (identical on every shard), route each key to its
    splitter bucket with a capacity-bounded all_to_all (4x-uniform
    slack, exact-worst-case retry on overflow), then sort locally.
    Returns [n_dev, cap] keys per shard (padded with i64 max) whose
    concatenation is globally sorted.
    """
    n_dev = mesh.devices.size
    # shape only — never fetch (keys may span non-addressable devices)
    m = int(np.prod(keys.shape)) // n_dev
    cap = min(m, 4 * m // n_dev + 64)
    out, dropped = _distributed_sort_jit(keys, mesh, cap)
    if int(device_fetch(dropped)) > 0:  # degenerate splitters
        out, dropped = _distributed_sort_jit(keys, mesh, m)
    return out


# --------------------------------------------------------------------------
# sharded sort that carries row payloads
# --------------------------------------------------------------------------
def _route_all_to_all_multi(leaves, dest, n_dev: int, pads, cap: int):
    """:func:`_route_all_to_all` for several arrays sharing one routing:
    the slot layout is computed once from ``dest`` and applied to every
    leaf (2-D leaves route row-wise).  Returns (received leaves, dropped).
    """
    m = dest.shape[0]
    order = jnp.argsort(dest)
    dest_sorted = dest[order]
    slot = (
        jnp.arange(m)
        - jnp.searchsorted(dest_sorted, jnp.arange(n_dev))[dest_sorted]
    )
    fits = slot < cap
    idx = jnp.where(fits, dest_sorted * cap + slot, n_dev * cap)
    received = []
    for leaf, pad in zip(leaves, pads):
        v = leaf[order]
        tail = v.shape[1:]
        flat = jnp.full((n_dev * cap + 1,) + tail, pad, dtype=v.dtype)
        flat = flat.at[idx].set(v)
        buf = flat[: n_dev * cap].reshape((n_dev, cap) + tail)
        out = jax.lax.all_to_all(buf, SHARD_AXIS, 0, 0)
        received.append(out.reshape((n_dev * cap,) + tail))
    dropped = jax.lax.psum(jnp.sum(~fits), SHARD_AXIS)
    return received, dropped


@partial(jax.jit, static_argnames=("mesh", "cap"))
def _distributed_sort_rows_jit(keys, payload, mesh, cap):
    n_dev = mesh.devices.size
    PAD = jnp.iinfo(jnp.int64).max

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(SHARD_AXIS), jax.tree.map(lambda _: P(SHARD_AXIS), payload)),
        out_specs=(
            P(SHARD_AXIS),
            jax.tree.map(lambda _: P(SHARD_AXIS), payload),
            P(),
        ),
        check_vma=False,
    )
    def run(local, rows):
        local = local.ravel()
        local_sorted = jnp.sort(local)
        qidx = (jnp.arange(n_dev) * local.shape[0]) // n_dev
        samples = jax.lax.all_gather(local_sorted[qidx], SHARD_AXIS).ravel()
        samples = jnp.sort(samples)
        idx = (jnp.arange(1, n_dev) * samples.shape[0]) // n_dev
        splitters = samples[idx]
        dest = jnp.searchsorted(splitters, local, side="right")
        leaves, treedef = jax.tree.flatten(rows)
        pads = [jnp.zeros((), l.dtype) for l in leaves]
        (rk, *rleaves), dropped = _route_all_to_all_multi(
            [local] + leaves, dest, n_dev, [PAD] + pads, cap
        )
        order = jnp.argsort(rk, stable=True)
        out_rows = jax.tree.unflatten(
            treedef, [l[order][None] for l in rleaves]
        )
        return rk[order][None], out_rows, dropped

    return run(keys, payload)


# --------------------------------------------------------------------------
# device lexsort (the barrier-1 duplicate-resolve sort)
# --------------------------------------------------------------------------
@partial(jax.jit, static_argnames=("n_keys",))
def _lexsort_perm_jit(keys_padded, n_keys: int):
    """Stable lexsort permutation of ``keys_padded`` ([n_keys + 1, m]
    i64; row order = np.lexsort convention, LAST key primary; the final
    row is the pad-validity key that sorts padding strictly last).

    np.lexsort is a cascade of stable sorts from the least-significant
    key up; composing ``perm = perm[argsort(k[perm], stable)]`` per key
    reproduces THE unique stable permutation — so the device result is
    bitwise the host result, not merely an equivalent order.
    """
    m = keys_padded.shape[1]
    perm = jnp.arange(m)
    for i in range(n_keys + 1):
        perm = perm[jnp.argsort(keys_padded[i][perm], stable=True)]
    return perm


def device_lexsort(keys, device=None, info=None):
    """``np.lexsort(keys)`` computed on a device -> i64[n] permutation.

    The single-device member of this module's sort family
    (:func:`distributed_sort_keys` / :func:`distributed_sort_rows` are
    the mesh members): the barrier-1 duplicate-resolve cascade
    (pipelines/markdup.resolve_duplicates) routes its packed summary
    keys through it, moving the measured 1.56 s of pure-host serial
    lexsort onto the chip.  ``keys`` follows the np.lexsort convention
    (sequence of equal-length i64 arrays, last key primary);
    ``device`` commits the sort to an explicit chip (the pool/mesh's
    device 0) or the default device when None.

    Inputs pad to the pow2 row grid (one compiled shape per decade of
    group count, not one per run) with an extra most-significant
    validity key that sorts the padding strictly last — ``perm[:n]`` is
    exactly the host permutation.  Any failure falls back to
    ``np.lexsort`` (bit-parity by construction), so a dead chip costs a
    warning, never a wrong resolve.

    ``info``: optional dict that receives ``{"device_sort": bool}`` —
    whether the device path actually DELIVERED the permutation (False
    on the fallback), so callers report the outcome, not the intent.
    """
    if info is not None:
        info["device_sort"] = False
    keys = [np.ascontiguousarray(k, np.int64) for k in keys]
    n = keys[0].shape[0] if keys else 0
    if n == 0 or not keys:
        return np.lexsort(tuple(keys)) if keys else np.zeros(0, np.int64)
    try:
        from adam_tpu.formats.batch import grid_rows
        from adam_tpu.parallel.device_pool import putter

        g = grid_rows(n)
        stack = np.zeros((len(keys) + 1, g), np.int64)
        for i, k in enumerate(keys):
            stack[i, :n] = k
        stack[len(keys), n:] = 1  # pad rows sort last, real order intact
        # deliberately NOT compile_ledger-tracked, unlike the other
        # streamed dispatch sites: the sort grid derives from the
        # BUCKET count, which only exists at the barrier itself — there
        # is no prewarm point ahead of it, so a ledger entry would
        # permanently flag a structurally unavoidable one-off compile
        # as an in-window "coverage gap" warning and drown the
        # actionable ones.  The jit executable cache still amortizes it
        # process-wide (the bench's warmup-run pattern pays it once).
        perm = _lexsort_perm_jit(putter(device)(stack), len(keys))
        from adam_tpu.utils.transfer import device_fetch

        out = np.asarray(device_fetch(perm[:n]), np.int64)
        if info is not None:
            info["device_sort"] = True
        return out
    except Exception:
        import logging

        logging.getLogger(__name__).warning(
            "device lexsort failed; falling back to the host np.lexsort "
            "(bit-identical)", exc_info=True,
        )
        return np.lexsort(tuple(keys))


def distributed_sort_rows(keys, payload, mesh):
    """Globally sort rows by i64 key across the mesh, *moving the rows*
    (sortByKey with payloads, AlignmentRecordRDDFunctions.scala:245-258 —
    not just the keys).

    ``payload``: pytree of arrays with leading dim == len(keys), sharded
    like ``keys``.  Returns (sorted_keys [n_dev, n_dev*cap], rows pytree
    [n_dev, n_dev*cap, ...], valid mask) — each shard's slice holds its
    splitter bucket locally sorted (capacity cap per sending shard), so
    concatenating shards in order yields the globally key-sorted rows;
    padding slots have key i64-max and are False in the mask.
    """
    n_dev = mesh.devices.size
    m = int(np.prod(np.shape(keys))) // n_dev
    cap = min(m, 4 * m // n_dev + 64)
    k, rows, dropped = _distributed_sort_rows_jit(keys, payload, mesh, cap)
    if int(device_fetch(dropped)) > 0:  # degenerate splitters: retry exact
        k, rows, dropped = _distributed_sort_rows_jit(keys, payload, mesh, m)
    valid = device_fetch(k) != np.iinfo(np.int64).max
    return k, rows, valid


# --------------------------------------------------------------------------
# distributed duplicate marking
# --------------------------------------------------------------------------
@partial(jax.jit, static_argnames=("mesh",))
def _markdup_columns_jit(batch: ReadBatch, mesh):
    from adam_tpu.pipelines.markdup import markdup_columns_local

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(_row_specs(batch),),
        out_specs=(P(SHARD_AXIS), P(SHARD_AXIS)),
        check_vma=False,
    )
    def run(local):
        # same traced body as the single-chip default path — the mesh
        # variant only adds the sharding
        return markdup_columns_local(
            local.start, local.end, local.flags,
            local.cigar_ops, local.cigar_lens, local.cigar_n,
            local.quals, local.lengths,
        )

    return run(batch)


def distributed_markdup(ds, mesh=None):
    """Duplicate marking over a row-sharded batch: the [N, L] work (5'
    clipped keys via the device CIGAR walk, quality scores via masked
    segment sums) runs sharded on the mesh; only the compact per-row
    columns come home for the group-subgroup-argmax cascade (the same
    driver-side lexsort the reference's groupBy shuffle feeds,
    MarkDuplicates.scala:66-128).  Marks are bitwise those of the
    single-chip :func:`adam_tpu.pipelines.markdup.mark_duplicates`.
    """
    from adam_tpu.pipelines import markdup as md

    mesh = mesh or genome_mesh()
    b = ds.batch.to_numpy()
    n = b.n_rows
    padded = pad_batch_for_mesh(ds.batch, mesh.devices.size).to_device()
    five, score = _markdup_columns_jit(padded, mesh)
    s = md.row_summary(
        ds, b,
        five_prime=device_fetch(five)[:n],
        score=device_fetch(score)[:n],
    )
    dup = md.resolve_duplicates(s)
    return ds.with_batch(
        b.replace(flags=md.apply_duplicate_flags(np.asarray(b.flags), dup))
    )


# --------------------------------------------------------------------------
# multihost telemetry aggregation
# --------------------------------------------------------------------------
def gather_host_telemetry(snapshot: dict | None = None) -> list[dict]:
    """Gather every host's telemetry snapshot at a merge barrier ->
    ``[snapshot_for_process_0, ..., snapshot_for_process_{n-1}]``.

    The observability face of the driver-aggregate pattern: where the
    reference's Spark listener collects per-executor task timings, the
    multihost pipeline calls this at its merge barrier (see
    tests/multihost_harness.py) so the report can show per-host skew
    (``adam_tpu.utils.telemetry.merge_snapshots``).  Snapshots ship as
    length-prefixed JSON bytes over a ``process_allgather`` — control
    plane only, never per-read data.  Must be called by ALL processes
    (it is a collective); single-process runs return ``[snapshot]``
    without touching the collective machinery.
    """
    import json

    from adam_tpu.utils import telemetry

    if snapshot is None:
        snapshot = telemetry.TRACE.snapshot()
    try:
        n_procs = jax.process_count()
    except Exception:
        n_procs = 1
    if n_procs == 1:
        return [snapshot]
    from jax.experimental import multihost_utils

    payload = np.frombuffer(
        json.dumps(snapshot, default=str).encode(), np.uint8
    )
    sizes = np.asarray(
        multihost_utils.process_allgather(np.int64(payload.size))
    ).reshape(-1)
    cap = int(sizes.max())
    buf = np.zeros(max(1, cap), np.uint8)
    buf[: payload.size] = payload
    gathered = np.asarray(multihost_utils.process_allgather(buf))
    gathered = gathered.reshape(n_procs, -1)
    return [
        json.loads(gathered[p, : int(sizes[p])].tobytes().decode())
        for p in range(n_procs)
    ]


# --------------------------------------------------------------------------
# halo (flank) exchange between genome-adjacent shards
# --------------------------------------------------------------------------
@partial(jax.jit, static_argnames=("flank", "mesh"))
def halo_exchange_right(chunks, mesh, flank: int):
    """Append each shard's first ``flank`` bases to its LEFT neighbor's
    chunk — the ppermute form of fragment flanking
    (FlankReferenceFragments: a fragment is extended with the start of
    the next fragment so windows spanning the boundary are complete).

    chunks: u8[n_shards, width] sharded on axis 0 -> returns
    u8[n_shards, width + flank] sharded the same way; the last shard's
    halo is BASE_PAD.
    """
    from adam_tpu.formats import schema

    n_dev = mesh.devices.size

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(SHARD_AXIS),),
        out_specs=P(SHARD_AXIS),
        check_vma=False,
    )
    def run(local):
        head = local[:, :flank]
        # send my head to my left neighbor (shard i -> i-1)
        perm = [(i, (i - 1) % n_dev) for i in range(n_dev)]
        halo = jax.lax.ppermute(head, SHARD_AXIS, perm)
        me = jax.lax.axis_index(SHARD_AXIS)
        halo = jnp.where(me == n_dev - 1, jnp.uint8(schema.BASE_PAD), halo)
        return jnp.concatenate([local, halo], axis=1)

    return run(chunks)
