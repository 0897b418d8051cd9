"""Device-vs-host bit-parity for the per-residue kernel backends.

The streamed flagship defaults to the ``device`` backend when a chip is
attached (``adam_tpu.pipelines.bqsr.bqsr_backend``): BQSR observe as a
jit scatter-add, BQSR apply as a jit table gather, markdup 5'-key/score
as jit reductions.  These tests pin every backend to the same bits —
the jit kernels run on the CPU jax backend here, so the *traced
programs* that ship to the chip are what is being differentially
tested, against the numpy twins and (where built) the native C++ walks.
"""

import itertools
import os
import sys

import numpy as np
import pytest

from adam_tpu.api.datasets import AlignmentDataset, GenotypeDataset
from adam_tpu.formats import schema
from adam_tpu.formats.batch import pack_reads
from adam_tpu.io import load_alignments
from adam_tpu.io.sam import SamHeader
from adam_tpu.models.dictionaries import (
    RecordGroup,
    RecordGroupDictionary,
    SequenceDictionary,
    SequenceRecord,
)
from adam_tpu.pipelines import bqsr as bq
from adam_tpu.pipelines import markdup as md

sys.path.insert(
    0, os.path.join(os.path.dirname(__file__), "..", "tools")
)

_counter = itertools.count()


# ---------------------------------------------------------------------------
# Backend resolution
# ---------------------------------------------------------------------------
def test_backend_env_override(monkeypatch):
    for b in bq.BACKENDS:
        monkeypatch.setenv("ADAM_TPU_BQSR_BACKEND", b)
        assert bq.bqsr_backend() == b
    monkeypatch.setenv("ADAM_TPU_BQSR_BACKEND", "Device")  # case-folded
    assert bq.bqsr_backend() == "device"
    monkeypatch.setenv("ADAM_TPU_BQSR_BACKEND", "spark")
    with pytest.raises(ValueError, match="spark"):
        bq.bqsr_backend()


def test_backend_explicit_override_beats_env(monkeypatch):
    monkeypatch.setenv("ADAM_TPU_BQSR_BACKEND", "numpy")
    assert bq.bqsr_backend("device") == "device"


def test_backend_topology_default(monkeypatch):
    """Without a chip (the CPU test harness) the default must be a host
    backend; with one, device."""
    monkeypatch.delenv("ADAM_TPU_BQSR_BACKEND", raising=False)
    monkeypatch.setattr(bq, "on_tpu", lambda device=None: False)
    assert bq.bqsr_backend() in ("native", "numpy")
    monkeypatch.setattr(bq, "on_tpu", lambda device=None: True)
    assert bq.bqsr_backend() == "device"


# ---------------------------------------------------------------------------
# WGS-shaped differential fixtures
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def wgs_ds(tmp_path_factory):
    """Small WGS-shaped dataset + known-sites table: indels, soft clips,
    planted SNPs, duplicates — every covariate path exercised."""
    from make_wgs_sam import make_wgs

    d = tmp_path_factory.mktemp("parity")
    sam = str(d / "w.sam")
    vcf = str(d / "w.vcf")
    make_wgs(sam, 2048, 100, n_contigs=2, contig_len=40_000,
             indel_every=800, snp_every=400, known_sites_out=vcf)
    ds = load_alignments(sam)
    known = GenotypeDataset.load(
        vcf, contig_names=ds.seq_dict.names
    ).snp_table()
    return ds, known


def test_observe_device_matches_numpy_wgs(wgs_ds):
    """The jit scatter-add histogram (the chip observe pass) and the
    numpy bincount twin produce identical tables, known-site masking
    included."""
    ds, known = wgs_ds
    t_dev, m_dev, rg_dev, g_dev = bq._observe_device(ds, known, "device")
    t_np, m_np, rg_np, g_np = bq._observe_device(ds, known, "numpy")
    assert rg_dev == rg_np and g_dev == g_np
    np.testing.assert_array_equal(np.asarray(t_dev), t_np)
    np.testing.assert_array_equal(np.asarray(m_dev), m_np)
    assert int(t_np.sum()) > 0 and int(m_np.sum()) > 0


def test_observe_device_matches_native_wgs(wgs_ds):
    """Device scatter-add vs the threaded C++ MD-walk histogram."""
    from adam_tpu import native

    if not native.available():
        pytest.skip("native library unavailable")
    ds, known = wgs_ds
    t_dev, m_dev, _, g_dev = bq._observe_device(ds, known, "device")
    t_nat, m_nat, _, g_nat = bq._observe_device(ds, known, "native")
    assert g_dev == g_nat
    np.testing.assert_array_equal(np.asarray(t_dev), t_nat)
    np.testing.assert_array_equal(np.asarray(m_dev), m_nat)


def test_apply_device_matches_host_wgs(wgs_ds):
    """The full observe->solve->apply pass is bit-identical across
    backends: recalibrated quals AND the stashed OQ sidecar."""
    ds, known = wgs_ds
    outs = {
        b: ds.recalibrate_base_qualities(known, backend=b)
        for b in ("device", "numpy")
    }
    ref = outs["numpy"].batch.to_numpy()
    assert (
        np.asarray(ref.quals) != np.asarray(ds.batch.to_numpy().quals)
    ).any(), "recalibration must change something for parity to mean anything"
    for b, out in outs.items():
        got = out.batch.to_numpy()
        np.testing.assert_array_equal(
            np.asarray(got.quals), np.asarray(ref.quals), err_msg=b
        )
        assert out.sidecar.orig_quals == outs["numpy"].sidecar.orig_quals


def test_apply_device_matches_native_wgs(wgs_ds):
    from adam_tpu import native

    if not native.available():
        pytest.skip("native library unavailable")
    ds, known = wgs_ds
    dev = ds.recalibrate_base_qualities(known, backend="device")
    nat = ds.recalibrate_base_qualities(known, backend="native")
    np.testing.assert_array_equal(
        np.asarray(dev.batch.to_numpy().quals),
        np.asarray(nat.batch.to_numpy().quals),
    )
    assert dev.sidecar.orig_quals == nat.sidecar.orig_quals


def test_apply_dispatch_finish_split_equals_eager(wgs_ds):
    """The streamed pipeline's double-buffered split (dispatch window
    i+1 before finishing window i) must equal the eager single-call
    apply."""
    ds, known = wgs_ds
    total, mism, _rg, gl = bq._observe_device(ds, known, "numpy")
    table = bq.solve_recalibration_table(total, mism)
    eager = bq.apply_recalibration(ds, table, gl, "device")
    h1 = bq.apply_recalibration_dispatch(ds, table, gl, "device")
    h2 = bq.apply_recalibration_dispatch(ds, table, gl, "device")
    out1 = bq.apply_recalibration_finish(h1)
    out2 = bq.apply_recalibration_finish(h2)
    for out in (out1, out2):
        np.testing.assert_array_equal(
            np.asarray(out.batch.to_numpy().quals),
            np.asarray(eager.batch.to_numpy().quals),
        )


# ---------------------------------------------------------------------------
# Golden-fixture parity (reference tree, skips when absent)
# ---------------------------------------------------------------------------
def test_observe_device_matches_golden(ref_resources):
    """The device scatter-add observe pass reproduces the GATK-derived
    bqsr1-ref.observed table exactly (the reference's own golden test,
    BaseQualityRecalibrationSuite.scala:30-47, run against the chip
    kernel instead of the host walk)."""
    from adam_tpu.models.snp_table import SnpTable

    ds = load_alignments(str(ref_resources / "bqsr1.sam"))
    snps = SnpTable.from_file(str(ref_resources / "bqsr1.snps"))
    t, m, rg_names, gl = bq._observe_device(ds, snps, "device")
    obs = bq.ObservationTable(np.asarray(t), np.asarray(m), rg_names, gl)
    ours = sorted(l for l in obs.to_csv().split("\n") if l)
    golden = sorted(
        l for l in (ref_resources / "bqsr1-ref.observed")
        .read_text().splitlines() if l
    )
    assert ours == golden


# ---------------------------------------------------------------------------
# Markdup device reductions
# ---------------------------------------------------------------------------
CONTIGS = ["0", "1", "ref0"]
SD = SequenceDictionary(tuple(SequenceRecord(n, 10_000_000) for n in CONTIGS))
RGD = RecordGroupDictionary((RecordGroup("m", library="lib"),))


def _read(ref, start, phred=20, clipped=0, neg=False, cigar=None,
          unmapped=False):
    name = f"r{next(_counter)}"
    if unmapped:
        return dict(name=name, flags=0x4, contig_idx=-1, start=-1, mapq=0,
                    cigar="*", seq="A" * 100, qual="5" * 100,
                    read_group_idx=0)
    cigar = cigar or (f"{clipped}S{100 - clipped}M" if clipped else "100M")
    return dict(
        name=name, flags=(0x10 if neg else 0), contig_idx=SD.index(ref),
        start=start, mapq=60, cigar=cigar, seq="A" * 100,
        qual=chr(phred + 33) * 100, read_group_idx=0,
    )


@pytest.fixture(scope="module")
def md_ds():
    """Markdup-shaped inputs: clipped 5' keys, both strands, unmapped
    rows, mixed quality rows — the score/key edge cases."""
    recs = [
        _read("0", 100), _read("0", 100, phred=30),
        _read("0", 102, clipped=2), _read("1", 50, neg=True),
        _read("1", 50, neg=True, clipped=4),
        _read("ref0", 7, cigar="50M10I40M"),
        _read("ref0", 7, cigar="30M200N70M"),
        _read("0", 9, unmapped=True),
        dict(name="mixedq", flags=0, contig_idx=0, start=1, mapq=60,
             cigar="4M", seq="ACGT",
             qual=chr(33 + 20) * 2 + chr(33 + 10) * 2, read_group_idx=0),
    ]
    batch, side = pack_reads(recs)
    return AlignmentDataset(
        batch, side, SamHeader(seq_dict=SD, read_groups=RGD)
    )


def test_markdup_columns_device_match_host(md_ds):
    """The jit [N, L] reductions (5'-clipped key + phred>=15 score)
    match the numpy row_summary columns bit-for-bit."""
    from adam_tpu.ops import cigar as cigar_ops

    b = md_ds.batch.to_numpy()
    five_dev, score_dev = md.markdup_columns_device(md_ds.batch)
    five_np = cigar_ops.five_prime_position_np(
        b.start, b.end, b.flags, b.cigar_ops, b.cigar_lens, b.cigar_n
    )
    quals = np.asarray(b.quals)
    in_read = np.arange(b.lmax)[None, :] < np.asarray(b.lengths)[:, None]
    score_np = np.where(in_read & (quals >= 15), quals, 0).sum(
        axis=1, dtype=np.int32
    )
    np.testing.assert_array_equal(five_dev, five_np)
    np.testing.assert_array_equal(score_dev, score_np)


def test_mark_duplicates_device_backend_matches_host(md_ds):
    """End-to-end duplicate flags agree between the device and numpy
    backends on a batch with duplicates to mark."""
    recs = [_read("0", 42, phred=30)] + [_read("0", 42) for _ in range(5)]
    batch, side = pack_reads(recs)
    ds = AlignmentDataset(batch, side, SamHeader(seq_dict=SD, read_groups=RGD))
    f_dev = np.asarray(
        ds.mark_duplicates(backend="device").batch.to_numpy().flags
    )
    f_np = np.asarray(
        ds.mark_duplicates(backend="numpy").batch.to_numpy().flags
    )
    assert (f_dev & schema.FLAG_DUPLICATE).sum() > 0
    np.testing.assert_array_equal(f_dev, f_np)
    f_dev2 = np.asarray(
        md_ds.mark_duplicates(backend="device").batch.to_numpy().flags
    )
    f_np2 = np.asarray(
        md_ds.mark_duplicates(backend="numpy").batch.to_numpy().flags
    )
    np.testing.assert_array_equal(f_dev2, f_np2)


# ---------------------------------------------------------------------------
# Streamed pipeline under the device backend
# ---------------------------------------------------------------------------
def test_streamed_device_backend_matches_numpy(tmp_path, monkeypatch):
    """The whole streamed flagship — markdup dispatch double-buffer,
    lazy device observe fetched at the merge barrier, double-buffered
    device apply, PartWriterPool sink — is bit-identical to the numpy
    backend run."""
    from make_wgs_sam import make_wgs

    from adam_tpu.io import context
    from adam_tpu.pipelines.streamed import transform_streamed

    path = str(tmp_path / "in.sam")
    make_wgs(path, 2048, 100, n_contigs=1, contig_len=30_000)
    outs = {}
    for b in ("device", "numpy"):
        monkeypatch.setenv("ADAM_TPU_BQSR_BACKEND", b)
        out = str(tmp_path / f"{b}.adam")
        stats = transform_streamed(path, out, window_reads=512)
        assert stats["bqsr_backend"] == b
        if b == "device":
            # the device run must actually take the device code paths
            assert "md_cols_fetch_s" in stats
            assert "apply_device_dispatch_s" in stats
        outs[b] = context.load_alignments(out).compact()
    ref = outs["numpy"].batch.to_numpy()
    got = outs["device"].batch.to_numpy()
    names_ref = list(outs["numpy"].sidecar.names)
    names_got = list(outs["device"].sidecar.names)
    order_ref = np.lexsort((np.asarray(ref.flags), np.asarray(names_ref, "S64")))
    order_got = np.lexsort((np.asarray(got.flags), np.asarray(names_got, "S64")))
    assert [names_ref[i] for i in order_ref] == [names_got[i] for i in order_got]
    np.testing.assert_array_equal(
        np.asarray(ref.flags)[order_ref], np.asarray(got.flags)[order_got]
    )
    L = min(ref.lmax, got.lmax)
    np.testing.assert_array_equal(
        np.asarray(ref.quals)[order_ref][:, :L],
        np.asarray(got.quals)[order_got][:, :L],
    )
    oq_ref = [outs["numpy"].sidecar.orig_quals[i] for i in order_ref]
    oq_got = [outs["device"].sidecar.orig_quals[i] for i in order_got]
    assert oq_ref == oq_got


# ---------------------------------------------------------------------------
# Satellite regressions
# ---------------------------------------------------------------------------
def test_pos_zero_mapped_read_does_not_spill_junk_bin(tmp_path):
    """A record flagged mapped but carrying POS=0 (start == -1, as some
    aligners emit for placed-but-unaligned mates) must be dropped by the
    spill filter, not land in a junk 'bin--00001' file of the previous
    contig."""
    from adam_tpu.parallel.partitioner import GenomeBins
    from adam_tpu.parallel.sharded_join import _spill_batches

    recs = [
        _read("0", 100),
        dict(name="pos0", flags=0, contig_idx=0, start=-1, mapq=0,
             cigar="100M", seq="A" * 100, qual="I" * 100, read_group_idx=0),
    ]
    batch, side = pack_reads(recs)
    header = SamHeader(seq_dict=SD, read_groups=RGD)
    bins = GenomeBins(1_000_000, SD)
    spill, n = _spill_batches(
        [(batch.to_numpy(), side, header)], bins, str(tmp_path)
    )
    try:
        touched = spill.touched_bins()
        assert all(b >= 0 for b in touched)
        # exactly the one genuinely-mapped read spilled
        assert sum(spill._counts[b] for b in touched) == 1
        assert not [
            f for f in os.listdir(str(tmp_path)) if "bin--" in f
        ]
    finally:
        spill.cleanup()


def test_part_writer_pool_roundtrip_and_error(tmp_path):
    """The double-buffered part writer writes every submitted part
    (readable back with the normal loader) and surfaces write errors at
    close()."""
    from adam_tpu.io import parquet

    recs = [_read("0", 10 + i) for i in range(6)]
    batch, side = pack_reads(recs)
    header = SamHeader(seq_dict=SD, read_groups=RGD)
    out = tmp_path / "parts"
    out.mkdir()
    pool = parquet.PartWriterPool(n_encoders=2, inflight_parts=2)
    for i in range(3):
        pool.submit(str(out / f"part-r-{i:05d}.parquet"), batch, side, header)
    pool.close()
    for i in range(3):
        back_batch, _side, _hdr = parquet.load_alignments(
            str(out / f"part-r-{i:05d}.parquet")
        )
        assert back_batch.n_rows == batch.n_rows

    bad = parquet.PartWriterPool(n_encoders=1, inflight_parts=1)
    bad.submit(
        str(tmp_path / "missing-dir" / "part.parquet"), batch, side, header
    )
    with pytest.raises(Exception):
        bad.close()
