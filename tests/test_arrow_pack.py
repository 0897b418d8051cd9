"""Packed-column pass C: on-device packing, zero-copy Arrow assembly,
and the adaptive sharded writer pool.

The acceptance contract (ISSUE 12): Parquet parts written through the
packed path are **byte-identical** to the legacy matrix path across
compressions, window shapes and backends (pool-device / mesh / host
fallback), the pack kernels are bit-parity twins of their numpy
counterparts, and the writer pool keeps its crash-consistency and
gauge contracts under K-way write sharding and adaptive growth.
"""

import hashlib
import importlib.machinery
import os
import sys
import threading

import numpy as np
import pyarrow as pa
import pytest

from adam_tpu.formats import schema
from adam_tpu.formats.batch import pack_reads
from adam_tpu.io import parquet
from adam_tpu.io.arrow_pack import (
    PackedQuals,
    index_name_array,
    pack_matrix_host,
    packed_qual_array,
)
from adam_tpu.io.sam import SamHeader
from adam_tpu.models.dictionaries import (
    RecordGroup,
    RecordGroupDictionary,
    SequenceDictionary,
    SequenceRecord,
)
from adam_tpu.ops import colpack
from adam_tpu.pipelines import bqsr as bq
from adam_tpu.utils import telemetry as tele

sys.path.insert(
    0, os.path.join(os.path.dirname(__file__), "..", "tools")
)

SD = SequenceDictionary((SequenceRecord("0", 1000),))
RGD = RecordGroupDictionary((RecordGroup("rg1"),))


# ---------------------------------------------------------------------------
# colpack kernels vs numpy twins
# ---------------------------------------------------------------------------
def test_pack_rows_kernel_matches_np():
    rng = np.random.default_rng(7)
    for n, w in ((1, 1), (5, 8), (64, 33), (128, 100)):
        mat = rng.integers(0, 256, (n, w)).astype(np.uint8)
        lens = rng.integers(0, w + 1, n).astype(np.int64)
        lens[:: max(1, n // 3)] = 0  # sprinkle empty rows
        total = int(lens.sum())
        size = n * w
        dev = np.asarray(colpack.pack_rows_kernel(mat, lens, size))
        host = colpack.pack_rows_np(mat, lens)
        assert dev.shape == (size,)
        np.testing.assert_array_equal(dev[:total], host)
        # the tail beyond the payload is zero fill, never read data
        assert not dev[total:].any() or total == size


def test_pack_rows_empty():
    out = colpack.pack_rows_np(np.zeros((0, 4), np.uint8), np.zeros(0))
    assert out.size == 0
    dev = np.asarray(
        colpack.pack_rows_kernel(
            np.zeros((1, 4), np.uint8), np.zeros(1, np.int64), 4
        )
    )
    assert dev.shape == (4,) and not dev.any()


def _mix_lengths(case, rng, n):
    """-> (lengths, valid, has_qual) of ``n`` rows for one length mix."""
    valid = np.ones(n, bool)
    has_qual = np.ones(n, bool)
    if case == "full100":
        lengths = np.full(n, 100)
    elif case == "cell_mix":  # the WGS cell: 12% of rows 60-99 nt
        lengths = np.where(rng.random(n) < 0.12, rng.integers(60, 100, n), 100)
    elif case == "trimmed":
        lengths = rng.integers(22, 33, n)
    elif case == "sparse":  # mostly empty rows: long shifts
        lengths = np.where(rng.random(n) < 0.02, rng.integers(1, 129, n), 0)
    elif case == "no_qual":
        lengths = rng.integers(60, 129, n)
        has_qual = rng.random(n) < 0.7
    else:  # random, with empty and invalid rows
        lengths = rng.integers(0, 129, n)
        lengths[::7] = 0
        valid = rng.random(n) < 0.9
    return lengths, valid, has_qual


@pytest.mark.parametrize("case,n,extra", [
    ("full100", 96, 0),
    ("cell_mix", 257, 0),
    ("trimmed", 200, 0),
    ("random", 300, 0),
    ("no_qual", 120, 0),
    ("random", 1, 0),
    ("trimmed", 40, 1000),
    ("sparse", 3000, 0),
])
def test_pack_rows_body_matches_np_whole_buffer(case, n, extra):
    """The shift network against its host twin over the whole
    ``u8[size]`` buffer: the packed prefix, then zeros to ``size``."""
    rng = np.random.default_rng(n)
    lengths, valid, has_qual = _mix_lengths(case, rng, n)
    mat = rng.integers(0, 256, (n, 128)).astype(np.uint8)
    for lens in (colpack.pack_lengths(lengths, valid, has_qual),
                 colpack.pack_lengths(lengths, valid)):
        size = n * 128 + extra
        dev = np.asarray(colpack.pack_rows_kernel(mat, lens, size))
        host = colpack.pack_rows_np(mat, lens)
        want = np.zeros(size, np.uint8)
        want[: host.size] = host
        assert dev.dtype == np.uint8
        np.testing.assert_array_equal(dev, want)


def test_base_decode_body_matches_lut():
    codes = np.arange(256, dtype=np.uint8).reshape(16, 16)
    dev = np.asarray(colpack.base_decode_body(codes))
    assert dev.dtype == np.uint8
    np.testing.assert_array_equal(dev, schema.BASE_DECODE_LUT256[codes])


def test_sanger_body_matches_lut():
    q = np.arange(256, dtype=np.uint8).reshape(16, 16)
    dev = np.asarray(colpack.sanger_body(q))
    np.testing.assert_array_equal(dev, schema.QUAL_SANGER_LUT256[q])


def test_fetch_grid_properties():
    for n in (1, 100, 4095, 4096, 4097, 123457, 10_000_000):
        g = colpack.fetch_grid(n)
        assert g >= n
        assert g >= 4096
        # over-fetch strictly bounded: < 1/16 of scale + quantum floor
        assert g - n < max(4096, 1 << max(0, n.bit_length() - 4)) + 1
    # bucketing collapses nearby sizes to one shape
    assert colpack.fetch_grid(1_000_001) == colpack.fetch_grid(1_000_002)


# ---------------------------------------------------------------------------
# Arrow builders
# ---------------------------------------------------------------------------
def test_index_name_array_matches_legacy():
    names = ["chr17", "", "µ-contig", "chr20"]
    idx = np.array([0, -1, 2, 3, 3, 1, -1, 0], np.int32)
    got = index_name_array(idx, names)
    lut = np.array(names + [None], dtype=object)
    want = pa.array(lut[np.where(idx >= 0, idx, len(names))], pa.string())
    assert got.type == want.type == pa.string()
    assert got.equals(want)
    # all-valid fast path (no validity buffer)
    got2 = index_name_array(np.array([1, 1, 0]), names)
    assert got2.null_count == 0
    assert got2.to_pylist() == ["", "", "chr17"]
    # empty dictionary / empty column
    assert index_name_array(np.zeros(0, np.int32), []).to_pylist() == []


def test_packed_quals_take():
    lens = np.array([3, 0, 2, 0, 4], np.int64)
    buf = np.arange(9, dtype=np.uint8)
    p = PackedQuals(buf, lens)
    # dropping only zero-length rows: the buffer is shared, not copied
    q = p.take(np.array([0, 2, 4]))
    assert q.buf is p.buf
    np.testing.assert_array_equal(q.lens, [3, 2, 4])
    # dropping a byte-bearing row falls back to the span gather
    r = p.take(np.array([0, 4]))
    np.testing.assert_array_equal(r.lens, [3, 4])
    np.testing.assert_array_equal(r.buf, np.r_[buf[:3], buf[5:]])


def test_packed_qual_array_matches_decoded():
    rng = np.random.default_rng(3)
    n, w = 32, 20
    quals = rng.integers(0, 41, (n, w)).astype(np.uint8)
    lens = rng.integers(0, w + 1, n).astype(np.int64)
    has_qual = rng.random(n) < 0.8
    pack_lens = np.where(has_qual, lens, 0)
    packed = pack_matrix_host(quals, pack_lens, schema.QUAL_SANGER_LUT256)
    got = packed_qual_array(packed, has_qual)
    from adam_tpu.formats.strings import StringColumn

    want = StringColumn.from_matrix(
        schema.QUAL_SANGER_LUT256[quals], pack_lens, has_qual.copy()
    ).to_arrow()
    assert got.equals(want)


# ---------------------------------------------------------------------------
# End-to-end byte identity: packed vs matrix Parquet parts
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def wgs_apply_case(tmp_path_factory):
    """A trimmed-length WGS-shaped window + its solved recalibration
    table (numpy observe/solve — the differential oracle)."""
    from make_wgs_sam import make_wgs

    from adam_tpu.api.datasets import AlignmentDataset

    d = tmp_path_factory.mktemp("arrowpack")
    sam = str(d / "w.sam")
    make_wgs(sam, 3000, read_len=60, seed=11, n_contigs=2,
             contig_len=60_000, trimmed_frac=0.5, trimmed_min=20,
             trimmed_max=30)
    ds = AlignmentDataset.load(sam)
    total, mism, _rg, gl = bq._observe_device(ds, backend="numpy")
    table = bq.solve_recalibration_table(total, mism)
    return ds, np.ascontiguousarray(table, np.uint8), gl


def _sha(path):
    return hashlib.sha256(open(path, "rb").read()).hexdigest()


def _write_part(tmp_path, tag, ds, compression, packed=None):
    path = str(tmp_path / f"part-{tag}.parquet")
    table = parquet.to_arrow_alignments(
        ds.batch, ds.sidecar, ds.header, packed=packed
    )
    parquet._write_encoded(table, path, compression)
    return path


def _pack2_on_handle(ds, table, gl, device=None, mesh=None):
    """Pass C's one program: the fused bases+quals pack2 dispatched on a
    resident handle (pool chip or mesh-sharded) -> (dataset, packed)."""
    from adam_tpu.parallel import device_pool as dp
    from adam_tpu.parallel import partitioner as part_mod

    b = ds.batch.to_numpy()
    if mesh is not None:
        rw = part_mod.mesh_resident_window(b, 0, mesh)
        table = mesh.put_replicated(table)
    else:
        rw = dp.make_resident_window(b, 0, device)
    handle = bq.apply_recalibration_dispatch(
        ds, table, gl, "device", device=device, mesh=mesh, resident=rw
    )
    rw.release()
    got, packed = bq.apply_recalibration_finish_packed(handle)
    assert packed.quals is not None and packed.bases is not None
    return got, packed


@pytest.mark.parametrize("compression", ["none", "snappy", "zstd"])
def test_packed_part_byte_identical_pool_device(
    wgs_apply_case, tmp_path, compression
):
    import jax

    ds, table, gl = wgs_apply_case
    ref = bq.apply_recalibration(ds, table, gl, "numpy")
    got, packed = _pack2_on_handle(ds, table, gl, jax.local_devices()[0])
    assert int(packed.quals.lens.sum()) == len(packed.quals.buf)
    assert int(packed.bases.lens.sum()) == len(packed.bases.buf)
    a = _write_part(tmp_path, f"ref-{compression}", ref, compression)
    b = _write_part(
        tmp_path, f"packed-{compression}", got, compression, packed=packed
    )
    assert _sha(a) == _sha(b)


def test_packed_part_byte_identical_mesh(wgs_apply_case, tmp_path):
    import jax

    from adam_tpu.parallel.partitioner import MeshPartitioner

    ds, table, gl = wgs_apply_case
    ref = bq.apply_recalibration(ds, table, gl, "numpy")
    mp = MeshPartitioner(jax.local_devices()[:2])
    got, packed = _pack2_on_handle(ds, table, gl, mesh=mp)
    a = _write_part(tmp_path, "mesh-ref", ref, "zstd")
    b = _write_part(tmp_path, "mesh-packed", got, "zstd", packed=packed)
    assert _sha(a) == _sha(b)


def test_packed_part_byte_identical_host_fallback(wgs_apply_case, tmp_path):
    """The host path (device lost / degrade) writes through packed=None
    and must equal the packed output too — the replay contract."""
    import jax

    ds, table, gl = wgs_apply_case
    ref = bq.apply_recalibration(ds, table, gl, "numpy")
    got, packed = _pack2_on_handle(ds, table, gl, jax.local_devices()[0])
    a = _write_part(tmp_path, "host", ref, "zstd")
    b = _write_part(tmp_path, "dev", got, "zstd", packed=packed)
    assert _sha(a) == _sha(b)


def _read(ref, start, L=8, name=None):
    seq = "ACGTACGT"[:L]
    return {
        "name": name or f"r{start}", "flags": 0, "contig_idx": 0,
        "start": start, "mapq": 60, "cigar": f"{L}M", "seq": seq,
        "qual": "I" * L, "mate_contig_idx": -1, "mate_start": -1,
        "tlen": 0, "read_group_idx": 0, "attrs": "", "md": str(L),
    }


def test_packed_part_max_length_and_invalid_rows(tmp_path):
    """Full-width rows (lens == lmax, the uniform fast path) plus
    invalid padding rows: the compaction drops them for free on the
    packed side (they carry no bytes)."""
    recs = [_read("0", 10 + i) for i in range(5)]
    batch, side = pack_reads(recs, round_rows_to=8)  # 3 invalid pad rows
    header = SamHeader(seq_dict=SD, read_groups=RGD)
    from adam_tpu.api.datasets import AlignmentDataset

    ds = AlignmentDataset(batch, side, header)
    b = batch.to_numpy()
    pack_lens = colpack.pack_lengths(b.lengths, b.valid, b.has_qual)
    assert (b.lengths[np.asarray(b.valid)] == b.lmax).all()
    packed = pack_matrix_host(
        np.asarray(b.quals), pack_lens, schema.QUAL_SANGER_LUT256
    )
    a = _write_part(tmp_path, "ml-ref", ds, "zstd")
    bpath = _write_part(tmp_path, "ml-packed", ds, "zstd", packed=packed)
    assert _sha(a) == _sha(bpath)


def test_packed_part_empty_window(tmp_path):
    """A window with zero rows encodes identically with and without a
    (vacuous) packed payload."""
    batch, side = pack_reads([])
    header = SamHeader(seq_dict=SD, read_groups=RGD)
    from adam_tpu.api.datasets import AlignmentDataset

    ds = AlignmentDataset(batch, side, header)
    packed = PackedQuals(np.zeros(0, np.uint8), np.zeros(0, np.int64))
    a = _write_part(tmp_path, "empty-ref", ds, "zstd")
    b = _write_part(tmp_path, "empty-packed", ds, "zstd", packed=packed)
    assert _sha(a) == _sha(b)


# ---------------------------------------------------------------------------
# Adaptive sharded writer pool
# ---------------------------------------------------------------------------
def test_sharded_writer_pool_roundtrip(tmp_path):
    recs = [_read("0", 10 + i) for i in range(4)]
    batch, side = pack_reads(recs)
    header = SamHeader(seq_dict=SD, read_groups=RGD)
    out = tmp_path / "parts"
    out.mkdir()
    published = []
    pool = parquet.PartWriterPool(
        n_encoders=2, inflight_parts=2, n_io=3, adaptive=False,
        on_published=published.append,
    )
    paths = [str(out / parquet.part_name(i)) for i in range(7)]
    for p in paths:
        pool.submit(p, batch, side, header)
    pool.close()
    assert sorted(published) == sorted(paths)
    for p in paths:
        back, _s, _h = parquet.load_alignments(p)
        assert back.n_rows == batch.n_rows
    assert not (out / parquet.TMP_DIR_NAME).exists()


def test_sharded_writer_pool_error_failfast(tmp_path):
    recs = [_read("0", 10)]
    batch, side = pack_reads(recs)
    header = SamHeader(seq_dict=SD, read_groups=RGD)
    pool = parquet.PartWriterPool(
        n_encoders=1, inflight_parts=1, n_io=2, adaptive=False
    )
    pool.submit(
        str(tmp_path / "missing" / "part-r-00000.parquet"),
        batch, side, header,
    )
    with pytest.raises(Exception):
        pool.close()


def test_writer_shards_resolution(monkeypatch):
    monkeypatch.delenv("ADAM_TPU_WRITER_SHARDS", raising=False)
    assert 1 <= parquet.resolve_writer_shards() <= 2
    assert parquet.resolve_writer_shards(5) == 5
    assert parquet.resolve_writer_shards(99) == 8  # clamped
    monkeypatch.setenv("ADAM_TPU_WRITER_SHARDS", "3")
    assert parquet.resolve_writer_shards() == 3
    monkeypatch.setenv("ADAM_TPU_WRITER_SHARDS", "soup")
    assert 1 <= parquet.resolve_writer_shards() <= 2  # warn + default


def test_writer_adaptive_env(monkeypatch):
    monkeypatch.delenv("ADAM_TPU_WRITER_ADAPTIVE", raising=False)
    assert parquet.writer_adaptive_enabled(True) is True
    monkeypatch.setenv("ADAM_TPU_WRITER_ADAPTIVE", "0")
    assert parquet.writer_adaptive_enabled(True) is False
    monkeypatch.setenv("ADAM_TPU_WRITER_ADAPTIVE", "1")
    assert parquet.writer_adaptive_enabled(False) is True


def test_adaptive_growth_bounded():
    pool = parquet.PartWriterPool(
        n_encoders=1, inflight_parts=1, adaptive=True, n_io=1,
        tracer=tele.Tracer(recording=True),
    )
    cap = pool._bound_cap
    assert cap >= 2  # affinity floor + io thread
    for _ in range(50):
        pool._maybe_grow(True)
    assert pool.inflight_bound == cap  # grew, then stopped at the cap
    fixed = parquet.PartWriterPool(
        n_encoders=1, inflight_parts=1, adaptive=False, n_io=1
    )
    for _ in range(50):
        fixed._maybe_grow(True)
    assert fixed.inflight_bound == 1
    pool.close()
    fixed.close()


def test_adaptive_growth_needs_sustained_gating():
    pool = parquet.PartWriterPool(
        n_encoders=1, inflight_parts=1, adaptive=True, n_io=1
    )
    start = pool.inflight_bound
    # isolated gated submits interleaved with fast ones never trip it
    for _ in range(8):
        pool._maybe_grow(True)
        pool._maybe_grow(False)
        pool._maybe_grow(False)
        pool._maybe_grow(False)
    assert pool.inflight_bound == start
    pool.close()


def test_depth_gauge_ordered_and_never_negative():
    """The queue-depth gauge is written under the depth lock: under a
    concurrent +1/-1 storm from K threads its samples can never go
    negative and the LAST sample equals the true final depth (0)."""
    tr = tele.Tracer(recording=True)
    pool = parquet.PartWriterPool(
        n_encoders=1, inflight_parts=4, n_io=2, adaptive=False, tracer=tr
    )

    def storm():
        for _ in range(200):
            pool._sample_depth(+1)
            pool._sample_depth(-1)

    threads = [threading.Thread(target=storm) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    g = tr.snapshot()["gauges"][tele.G_POOL_DEPTH]
    assert g["min"] >= 0
    assert g["last"] == 0
    pool.close()


def test_encode_byte_counters(tmp_path):
    recs = [_read("0", 10 + i) for i in range(4)]
    batch, side = pack_reads(recs)
    header = SamHeader(seq_dict=SD, read_groups=RGD)
    tr = tele.Tracer(recording=True)
    pool = parquet.PartWriterPool(
        n_encoders=1, inflight_parts=1, n_io=1, adaptive=False, tracer=tr
    )
    pool.submit(str(tmp_path / parquet.part_name(0)), batch, side, header)
    pool.close()
    c = tr.snapshot()["counters"]
    assert c[tele.C_ENCODE_BYTES_IN] > 0
    assert c[tele.C_ENCODE_BYTES_OUT] > 0


# ---------------------------------------------------------------------------
# bench-diff derived stage keys
# ---------------------------------------------------------------------------
def _load_bench_diff():
    path = os.path.join(
        os.path.dirname(__file__), "..", "scripts", "bench-diff"
    )
    loader = importlib.machinery.SourceFileLoader("bench_diff_mod", path)
    return loader.load_module()


def test_bench_diff_stage_keys(tmp_path):
    bd = _load_bench_diff()
    snap = {
        "spans": {
            "streamed.pass_c": {"total_s": 5.0},
            "streamed.apply.dispatch": {"total_s": 1.0},
            "streamed.apply.fetch": {"total_s": 0.5},
            "device.pool.prewarm.pass_c": {"total_s": 0.5},
            "streamed.write_wait": {"total_s": 2.0},
        },
        "counters": {},
        "device_spans": {},
    }
    keys = bd._collect_snapshot(snap)
    assert keys["stages.apply_split_s"] == (3.0, "lower")
    assert keys["stages.apply_split_plus_write_wait_s"] == (5.0, "lower")
    # the require-factor gate consumes the combined key end to end
    import json

    old = tmp_path / "old.json"
    new = tmp_path / "new.json"
    old.write_text(json.dumps(snap))
    fast = json.loads(json.dumps(snap))
    for name in fast["spans"]:
        fast["spans"][name]["total_s"] /= 10.0
    new.write_text(json.dumps(fast))
    rc = bd.main([
        str(old), str(new), "--json",
        "--require-factor", "stages.apply_split_plus_write_wait_s=5",
    ])
    assert rc == 0


def test_bench_diff_baseline_dir(tmp_path, capsys):
    """--baseline-dir picks the newest BENCH_r*.json (round number
    wins, mtime breaks ties) so CI never hardcodes the old filename;
    naming the baseline both ways (or neither) is a usage error."""
    import json

    bd = _load_bench_diff()
    snap = {
        "spans": {"streamed.pass_c": {"total_s": 5.0}},
        "counters": {},
        "device_spans": {},
    }
    rounds = tmp_path / "rounds"
    rounds.mkdir()
    (rounds / "BENCH_r1.json").write_text(json.dumps(snap))
    (rounds / "BENCH_r10_gpu.json").write_text(json.dumps(snap))
    (rounds / "notes.json").write_text("{}")  # never a candidate
    assert bd.newest_bench_artifact(str(rounds)).endswith(
        "BENCH_r10_gpu.json")

    new = tmp_path / "new.json"
    new.write_text(json.dumps(snap))
    assert bd.main([str(new), "--baseline-dir", str(rounds)]) == 0
    capsys.readouterr()

    # both spellings at once, or neither: usage error, not a diff
    assert bd.main([str(rounds / "BENCH_r1.json"), str(new),
                    "--baseline-dir", str(rounds)]) == 2
    assert bd.main([str(new)]) == 2
    assert "exactly one way" in capsys.readouterr().err
    # an empty dir is a clean error, not a traceback
    empty = tmp_path / "empty"
    empty.mkdir()
    assert bd.main([str(new), "--baseline-dir", str(empty)]) != 0
