"""Multi-device tests on the 8-virtual-CPU mesh (the reference's
local[N] Spark analog)."""

import os
import pathlib

import jax
import numpy as np
import pytest

from adam_tpu.formats import schema
from adam_tpu.io import load_alignments
from adam_tpu.models.dictionaries import SequenceDictionary, SequenceRecord
from adam_tpu.parallel import dist, mesh as mesh_mod, partitioner


@pytest.fixture(scope="module")
def mesh():
    assert len(jax.devices()) == 8, "conftest should force 8 CPU devices"
    return mesh_mod.genome_mesh()


def test_position_partitioner():
    sd = SequenceDictionary(
        (SequenceRecord("1", 1000), SequenceRecord("2", 1000))
    )
    part = partitioner.position_partition(
        sd, np.array([0, 0, 1, 1, -1]), np.array([0, 999, 0, 999, -1]), 4
    )
    np.testing.assert_array_equal(part, [0, 1, 2, 3, 4])
    shards = partitioner.shard_rows_by_position(
        sd, np.array([0, 1, -1]), np.array([10, 10, -1]), 2
    )
    assert [list(s) for s in shards] == [[0], [1, 2]]


def test_region_partitioner():
    sd = SequenceDictionary(
        (SequenceRecord("1", 250), SequenceRecord("2", 100))
    )
    bins = partitioner.region_partition(
        sd, np.array([0, 0, 1, -1]), np.array([0, 240, 50, -1]), 100
    )
    np.testing.assert_array_equal(bins, [0, 2, 3, -1])


def test_distributed_flagstat_matches_local(ref_resources, mesh):
    ds = load_alignments(str(ref_resources / "reads12.sam"))
    failed_d, passed_d = dist.distributed_flagstat(ds.batch, mesh)
    failed_l, passed_l = ds.flagstat()
    assert passed_d == passed_l
    assert failed_d == failed_l


def test_distributed_kmers_match_local(ref_resources, mesh):
    ds = load_alignments(str(ref_resources / "small.sam"))
    local = ds.count_kmers(11)
    distributed = dist.distributed_count_kmers(ds.batch, 11, mesh)
    assert distributed == local


def test_distributed_markdup_matches_local(ref_resources, mesh):
    """Mesh-sharded markdup (device 5' keys + scores, driver cascade)
    marks bitwise what the single-chip path marks."""
    ds = load_alignments(str(ref_resources / "reads12.sam"))
    local = ds.mark_duplicates()
    distributed = dist.distributed_markdup(ds, mesh)
    np.testing.assert_array_equal(
        np.asarray(local.batch.flags), np.asarray(distributed.batch.flags)
    )


def test_distributed_sort_rows(mesh):
    """sortByKey with payloads: rows (not just keys) cross the mesh and
    come back globally key-ordered, nothing lost."""
    rng = np.random.default_rng(3)
    n = 8 * 64
    keys = rng.integers(0, 2**40, n).astype(np.int64)
    payload = {
        "a": np.arange(n, dtype=np.int32),
        "m": rng.integers(0, 255, (n, 5)).astype(np.uint8),
    }
    import jax.numpy as jnp

    k, rows, valid = dist.distributed_sort_rows(
        jnp.asarray(keys), jax.tree.map(jnp.asarray, payload), mesh
    )
    k = np.asarray(k).ravel()
    vmask = valid.ravel()
    real_keys = k[vmask]
    assert len(real_keys) == n and (np.diff(real_keys) >= 0).all()
    a = np.asarray(rows["a"]).reshape(-1)[vmask]
    m = np.asarray(rows["m"]).reshape(-1, 5)[vmask]
    # every row arrived exactly once, attached to its own key
    order = np.argsort(keys, kind="stable")
    np.testing.assert_array_equal(np.sort(a), np.arange(n))
    np.testing.assert_array_equal(keys[a], real_keys)
    np.testing.assert_array_equal(m, payload["m"][a])


def test_distributed_observe_matches_local(ref_resources, mesh):
    from adam_tpu.pipelines import bqsr

    ds = load_alignments(str(ref_resources / "bqsr1.sam"))
    obs_local = bqsr.build_observation_table(ds)

    # rebuild the same masks, then aggregate across the mesh
    import adam_tpu.ops.cigar as cigar_ops
    import jax.numpy as jnp
    from adam_tpu.ops.mdtag import batch_md_arrays

    b = ds.batch.to_numpy()
    is_mm, _, has_md = batch_md_arrays(ds.batch, ds.sidecar)
    flags = np.asarray(b.flags)
    read_ok = (
        np.asarray(b.valid)
        & ((flags & schema.FLAG_UNMAPPED) == 0)
        & ((flags & 0x900) == 0)
        & ((flags & schema.FLAG_DUPLICATE) == 0)
        & ((flags & schema.FLAG_FAILED_QC) == 0)
        & np.asarray(b.has_qual)
        & (np.asarray(b.mapq) > 0)
        & (np.asarray(b.mapq) != 255)
        & has_md
    )
    ref_pos = np.asarray(
        cigar_ops.reference_positions(
            jnp.asarray(b.cigar_ops), jnp.asarray(b.cigar_lens),
            jnp.asarray(b.cigar_n), jnp.asarray(b.start), b.lmax,
        )
    )
    quals = np.asarray(b.quals)
    residue_ok = (
        (quals > 0) & (quals < schema.QUAL_PAD) & (np.asarray(b.bases) < 4)
        & (ref_pos >= 0)
    )
    n_rg = len(ds.read_groups) + 1
    total_d, mism_d = jax.tree.map(
        np.asarray,
        dist.distributed_observe(ds.batch, residue_ok, is_mm, read_ok, n_rg, mesh),
    )
    # the local table is lane-grid-aligned (cycle axis centered at
    # obs_local.lmax >= b.lmax); compare the overlapping cycle window
    gl, lm = obs_local.lmax, b.lmax
    sl = np.s_[:, :, gl - lm : gl + lm + 1, :]
    np.testing.assert_array_equal(total_d, obs_local.total[sl])
    np.testing.assert_array_equal(mism_d, obs_local.mismatches[sl])
    assert obs_local.total.sum() == total_d.sum()


def test_distributed_sort(mesh):
    rng = np.random.default_rng(0)
    keys = rng.integers(0, 2**40, size=8 * 64, dtype=np.int64)
    out = np.asarray(dist.distributed_sort_keys(keys, mesh)).ravel()
    got = out[out != np.iinfo(np.int64).max]
    np.testing.assert_array_equal(got, np.sort(keys))


def test_halo_exchange(mesh):
    chunks = np.arange(8 * 16, dtype=np.uint8).reshape(8, 16) % 250
    out = np.asarray(dist.halo_exchange_right(chunks, mesh, 4))
    assert out.shape == (8, 20)
    np.testing.assert_array_equal(out[:, :16], chunks)
    for s in range(7):
        np.testing.assert_array_equal(out[s, 16:], chunks[s + 1, :4])
    assert (out[7, 16:] == schema.BASE_PAD).all()


def test_two_process_distributed():
    """Real multi-process jax.distributed: two OS processes, one CPU
    device each, genome mesh spanning both — the collectives cross a
    process boundary over gRPC (SURVEY §2.6's DCN requirement)."""
    import socket
    import subprocess
    import sys

    with socket.socket() as s:
        s.bind(("localhost", 0))
        port = s.getsockname()[1]
    coord = f"localhost:{port}"
    harness = str(pathlib.Path(__file__).parent / "multihost_harness.py")
    env = {k: v for k, v in os.environ.items()}
    procs = [
        subprocess.Popen(
            [sys.executable, harness, coord, "2", str(pid)],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            env=env,
        )
        for pid in range(2)
    ]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=300)
            outs.append(out)
    finally:
        for p in procs:
            p.kill()
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"proc {pid} failed:\n{out[-3000:]}"
        assert "HARNESS OK" in out, f"proc {pid} output:\n{out[-3000:]}"


def test_partition_by_contig():
    """ReferencePartitioner semantics: same contig -> same partition,
    unplaced rows -> the dedicated last partition."""
    ci = np.array([0, 1, 0, 2, -1, 1])
    part = partitioner.partition_by_contig(ci, 3)
    assert part[0] == part[2]
    assert part[1] == part[5]
    assert part[4] == 2
    shards = partitioner.shard_rows_by_contig(ci, 3)
    assert sorted(np.concatenate(shards).tolist()) == list(range(6))


def test_partition_by_contig_sparse_ids():
    """Sparse/high contig ids must not collide while partitions sit empty:
    ranks, not raw ids, feed the modulo (default = one partition per
    contig present)."""
    ci = np.array([7, 7, 40, 40, 1000, -1])
    part = partitioner.partition_by_contig(ci)
    mapped = part[[0, 2, 4]]
    assert len(set(mapped.tolist())) == 3  # distinct contigs, distinct parts
    assert part[5] == part.max()  # unplaced -> dedicated last partition


def test_host_shuffle_bam_to_shards(tmp_path):
    """Out-of-core genome shuffle: windowed BAM -> per-bin Parquet shards
    with no whole-dataset residency (SURVEY §2.6's host-level exchange
    for data exceeding HBM)."""
    import sys

    from adam_tpu import native

    if not native.available():
        pytest.skip("native codec unavailable")
    sys.path.insert(0, "/root/repo/tools")
    from make_synth_sam import make_sam

    from adam_tpu.api.datasets import AlignmentDataset
    from adam_tpu.parallel import host_shuffle

    sam_p = tmp_path / "s.sam"
    make_sam(str(sam_p), 6000, 100)
    ds = AlignmentDataset.load(str(sam_p))
    bam_p = tmp_path / "s.bam"
    ds.save(str(bam_p))

    paths = host_shuffle.shuffle_bam_to_shards(
        str(bam_p), 4, str(tmp_path / "shards"), batch_reads=1000
    )
    assert len(paths) >= 4
    total = 0
    prev_max = -1
    for batch, side, header in host_shuffle.iter_shards(paths):
        b = batch.to_numpy()
        v = np.asarray(b.valid)
        total += int(v.sum())
        starts = np.asarray(b.start)[v & (np.asarray(b.contig_idx) >= 0)]
        if len(starts):
            # genome-bin shards are globally range-ordered
            assert starts.min() > prev_max - 60_000_000 // 4
            prev_max = max(prev_max, int(starts.max()))
    assert total == 6000


@pytest.mark.parametrize("n_procs,n_shards", [(2, 4), (8, 16)])
def test_composed_transform_n_processes(tmp_path, n_procs, n_shards):
    """The COMPOSED flagship transform across real OS processes over a
    shared raw shard store — summaries/candidates exchange via spill
    files, observation tables merge with a cross-process device psum —
    must equal the monolithic single-process transform bit-for-bit on
    the output keys (SURVEY §2.6: the reference's whole execution model
    is this exchange, via Spark; the reference's local[N] suites test
    real shuffle paths at arbitrary N the same way,
    ADAMFunSuite.scala:22-29).  n=8 exercises the shard-store/psum
    design at a process count where contention and per-process RSS
    behave differently than at 2; each process's peak RSS must stay
    under a fixed budget."""
    import sys

    sys.path.insert(0, str(pathlib.Path(__file__).parent.parent / "tools"))
    from make_wgs_sam import make_wgs

    from tests.multihost_harness import run_composition

    from adam_tpu.io import context
    from adam_tpu.io.sam import iter_sam_batches
    from adam_tpu.parallel import host_shuffle

    sam = str(tmp_path / "in.sam")
    make_wgs(sam, 3000, 100, n_contigs=2, contig_len=30_000)

    shard_dir = str(tmp_path / "shards")
    host_shuffle.shuffle_alignments_to_shards(
        iter_sam_batches(sam, batch_reads=1024), n_shards, shard_dir,
        fmt="raw",
    )

    # monolithic expectation
    mono = (
        context.load_alignments(sam)
        .mark_duplicates()
        .realign_indels()
        .recalibrate_base_qualities()
    )

    out_dir = str(tmp_path / "out.adam")
    results = run_composition(n_procs, shard_dir, out_dir)
    for pid, (_out, rss_gb) in enumerate(results):
        # budget: jax runtime + one shard's columns; the whole point of
        # the shard store is that per-process memory does not scale with
        # the dataset or the process count
        assert rss_gb < 1.5, (
            f"proc {pid} peak RSS {rss_gb} GB over budget"
        )

    got = context.load_alignments(out_dir)

    def keyed(d):
        b = d.batch.to_numpy()
        rows = []
        for i in range(b.n_rows):
            if not b.valid[i]:
                continue
            nc = int(b.cigar_n[i])
            rows.append((
                d.sidecar.names[i],
                int(b.flags[i]),
                int(b.start[i]),
                tuple(b.cigar_lens[i, :nc].tolist()),
                tuple(b.cigar_ops[i, :nc].tolist()),
                b.bases[i, : int(b.lengths[i])].tobytes(),
                int(b.quals[i, : int(b.lengths[i])].sum()),
                d.sidecar.md[i],
            ))
        return sorted(rows)

    assert len(got) == len(mono)
    assert keyed(got) == keyed(mono)


def test_composed_mesh_transform_capacity_retry(mesh, monkeypatch, tmp_path):
    """Drive the capacity-bounded all_to_all through the COMPOSED mesh
    transform (sort-rows -> markdup -> k-mers over one dataset) at a
    skew/size that forces the exact-capacity second exchange round
    inside the public APIs — not just the toy jit probes — and pin the
    results against the monolithic path."""
    import jax.numpy as jnp

    from adam_tpu.io import context
    from adam_tpu.parallel import dist

    n_dev = mesh.devices.size
    n, L = 512 * n_dev, 32
    # every read: same position, same poly-A sequence — one giant
    # duplicate pileup whose sort keys AND k-mer keys all route to a
    # single destination shard (maximal skew; the slack capacity is
    # 4/n_dev of the per-shard rows, so this must overflow)
    sam = tmp_path / "skew.sam"
    with open(sam, "w") as fh:
        fh.write("@HD\tVN:1.5\n@SQ\tSN:chr1\tLN:100000\n")
        for i in range(n):
            fh.write(
                f"r{i}\t0\tchr1\t501\t60\t{L}M\t*\t0\t0\t"
                f"{'A' * L}\t{'I' * L}\tMD:Z:{L}\n"
            )
    ds = context.load_alignments(str(sam))

    calls = {"sort": 0, "kmers": 0}
    orig_sort = dist._distributed_sort_rows_jit
    orig_kmers = dist._distributed_kmers_jit

    def sort_spy(*a, **k):
        calls["sort"] += 1
        return orig_sort(*a, **k)

    def kmers_spy(*a, **k):
        calls["kmers"] += 1
        return orig_kmers(*a, **k)

    monkeypatch.setattr(dist, "_distributed_sort_rows_jit", sort_spy)
    monkeypatch.setattr(dist, "_distributed_kmers_jit", kmers_spy)

    # composed: mesh sort (rows move), markdup over the sorted dataset,
    # k-mer exchange over the same batch
    b = ds.batch.to_numpy()
    keys = jnp.asarray(
        (np.asarray(b.contig_idx, np.int64) << 40)
        | np.asarray(b.start, np.int64)
    )
    k_out, rows, valid = dist.distributed_sort_rows(
        keys, {"row": jnp.arange(ds.batch.n_rows, dtype=jnp.int32)}, mesh
    )
    assert calls["sort"] == 2, (
        "maximal key skew must overflow the slack round and trigger "
        "the exact-capacity retry inside distributed_sort_rows"
    )
    order = np.asarray(rows["row"]).reshape(-1)[valid.ravel()]
    assert len(order) == n
    sorted_ds = ds.take_rows(order)

    md = dist.distributed_markdup(sorted_ds, mesh)
    mono = sorted_ds.mark_duplicates()
    np.testing.assert_array_equal(
        np.asarray(md.batch.flags), np.asarray(mono.batch.flags)
    )
    # the pileup marks all but one primary as duplicates
    n_dup = ((np.asarray(md.batch.flags) & schema.FLAG_DUPLICATE) != 0).sum()
    assert n_dup == n - 1

    counts = dist.distributed_count_kmers(md.batch, 21, mesh=mesh)
    assert calls["kmers"] == 2, (
        "identical k-mer keys must overflow and retry inside "
        "distributed_count_kmers"
    )
    assert counts == {"A" * 21: n * (L - 21 + 1)}


def test_capacity_bound_overflow_and_skew_split(mesh):
    """Stress the capacity-bounded all_to_all at realistic shapes with
    pathological skew: >=256 rows/device of IDENTICAL k-mers routes
    every key to one shard, overflowing the slack capacity — the
    dropped counter must fire and the exact-capacity retry must still
    produce exact counts.  Ditto the row-carrying distributed sort, and
    the empty-target -1 - start/3000 skew split must actually spread."""
    import jax.numpy as jnp

    from adam_tpu.formats.batch import ReadBatch, pack_reads
    from adam_tpu.parallel.dist import (
        _distributed_kmers_jit,
        distributed_count_kmers,
        pad_batch_for_mesh,
    )

    n_dev = mesh.devices.size
    n, L, k = 256 * n_dev, 32, 21
    # every read is poly-A: every k-mer is the SAME key, so every source
    # shard routes its entire send to one destination — the per-(source,
    # dest) capacity bound must overflow
    seq = "A" * L
    recs = [
        dict(name=f"r{i}", flags=0, contig_idx=0, start=i, mapq=60,
             cigar=f"{L}M", seq=seq, qual="I" * L, md=str(L))
        for i in range(n)
    ]
    batch, _side = pack_reads(recs)

    padded = pad_batch_for_mesh(batch, n_dev).to_device()
    m = (padded.n_rows // n_dev) * (padded.lmax - k + 1)
    cap = min(m, 4 * m // n_dev + 64)
    _s, _c, _h, dropped = _distributed_kmers_jit(
        padded.bases, padded.lengths, padded.valid, k, mesh, cap
    )
    assert int(dropped) > 0, (
        "skewed keys must overflow the slack capacity (the bound "
        "never binding means the stress is not a stress)"
    )
    # the public API retries at exact capacity: counts must be exact
    counts = distributed_count_kmers(batch, k, mesh=mesh)
    total = sum(counts.values())
    assert total == n * (L - k + 1)
    assert max(counts.values()) >= n  # the skewed keys all counted

    # row-carrying distributed sort under the same skew (all-equal keys)
    from adam_tpu.parallel.dist import distributed_sort_keys

    keys = jnp.zeros(n, jnp.int64)  # maximal skew: one destination
    out = np.asarray(distributed_sort_keys(keys, mesh)).ravel()
    real = out[out != np.iinfo(np.int64).max]
    assert len(real) == n and (real == 0).all()

    # empty-target skew split: unmatched reads spread over -1 - start/3000
    from adam_tpu.pipelines import realign as ra

    starts = np.arange(n, dtype=np.int64) * 500
    tidx = ra.map_reads_to_targets_overlap(
        np.zeros(n, np.int64), starts, starts + L,
        np.ones(n, bool),
        np.zeros(0, np.int64), np.zeros(0, np.int64), np.zeros(0, np.int64),
    )
    assert (tidx < 0).all()
    n_bins = len(np.unique(tidx))
    assert n_bins == len(np.unique(-1 - starts // 3000))
    assert n_bins >= n * 500 // 3000  # genuinely spread, not one bin
