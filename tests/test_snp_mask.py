"""The known-SNP mask of BQSR's observe pass.

``SnpTable.mask_routed`` answers each residue from a u8 map of the sites
inside the batch's own reference span, and falls back to a per-contig
``searchsorted`` over the rows that hold a site when the span is too
wide for the map.  Every route must give, bit for bit, a
plain per-residue set-membership test; the counters say which route a
call took.  An observe call with known SNPs times the mask in one
``bqsr.observe.snp_mask`` span inside its ``bqsr.observe.prep``."""

import os
import sys

import numpy as np
import pytest

from adam_tpu.api.datasets import GenotypeDataset
from adam_tpu.io import load_alignments
from adam_tpu.models.snp_table import SnpTable
from adam_tpu.ops import cigar as cigar_ops
from adam_tpu.pipelines import bqsr as bq
from adam_tpu.utils import telemetry as tele

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))

N, L = 800, 50
CONTIG_LEN = 40_000
# chrUn is in the batch's dictionary and absent from the table
NAMES = ["chr1", "chr2", "chrUn"]


def _oracle(table: SnpTable, names, contig_idx, positions) -> np.ndarray:
    sites = {(c, int(p)) for c, arr in table.table.items() for p in arr}
    out = np.zeros(positions.shape, bool)
    for r, ci in enumerate(contig_idx):
        if not 0 <= ci < len(names):
            continue
        for j, p in enumerate(positions[r]):
            out[r, j] = p >= 0 and (names[ci], int(p)) in sites
    return out


def _batch(density: int, n_contigs: int, wide: bool, seed: int):
    """A coordinate-sorted batch over ``n_contigs`` of the table's
    contigs, 5% of positions at -1, a few rows on contig -1 or on chrUn,
    and an eighth of the reads past the contig's end (and its last
    site).  ``wide`` moves each contig's last tenth of rows a megabase
    on: a span too wide for the map."""
    rng = np.random.default_rng(seed)
    table = SnpTable({
        name: rng.choice(CONTIG_LEN, CONTIG_LEN // density, replace=False)
        for name in NAMES[:2]
    })
    contig_idx = np.sort(rng.integers(0, n_contigs, N)).astype(np.int32)
    start = np.empty(N, np.int64)
    for c in range(n_contigs):
        rows = contig_idx == c
        s = np.sort(rng.integers(CONTIG_LEN - 7_000, CONTIG_LEN + 1_000,
                                 rows.sum()))
        if wide:
            s[-(len(s) // 10):] += 1_000_000
        start[rows] = s
    odd = rng.random(N) < 0.05
    contig_idx[odd] = rng.choice([-1, 2], odd.sum())
    # aligned runs with deletions: positions rise by 1 or 2
    positions = start[:, None] + np.cumsum(
        rng.integers(1, 3, (N, L)), axis=1) - 1
    positions[rng.random((N, L)) < 0.05] = -1
    return table, contig_idx, positions, start


def _hit_rows(table, contig_idx, positions, lo) -> int:
    """Rows on a contig of the table with a site in [lo, last position]."""
    n = 0
    for r, ci in enumerate(contig_idx):
        sites = table.table.get(NAMES[ci]) if ci >= 0 else None
        hi = positions[r].max()
        if sites is not None and hi >= 0:
            n += bool(((sites >= max(lo[r], 0)) & (sites <= hi)).any())
    return n


# (site density 1/x, contigs, wide span, route)
CASES = {
    f"1in{d}-{c}contig": (d, c, False, "map")
    for d in (900, 50, 3) for c in (1, 2)
}
CASES.update({
    f"wide-1in{d}-{c}contig": (d, c, True, "fallback")
    for d, c in ((900, 1), (50, 2))
})


@pytest.fixture
def trace():
    rec = tele.TRACE.recording
    tele.TRACE.reset()
    tele.TRACE.recording = True
    yield tele.TRACE
    tele.TRACE.recording = rec
    tele.TRACE.reset()


@pytest.mark.parametrize("with_start", [True, False],
                         ids=["start", "no-start"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_mask_is_set_membership(trace, case, with_start):
    density, n_contigs, wide, route = CASES[case]
    table, contig_idx, positions, start = _batch(
        density, n_contigs, wide, seed=len(case) + density)
    got = bq.known_snp_mask(table, NAMES, contig_idx, positions,
                            start if with_start else None)
    want = _oracle(table, NAMES, contig_idx, positions)
    assert want.any() and got.dtype == bool
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(
        table.mask_positions(NAMES, contig_idx, positions), want)

    c = trace.snapshot()["counters"]
    assert c["bqsr.snp_mask.rows"] == N
    lo = start if with_start else np.where(
        positions >= 0, positions, np.iinfo(np.int64).max).min(axis=1)
    hits = c["bqsr.snp_mask.hit_rows"]
    assert hits == _hit_rows(table, contig_idx, positions, lo)
    fallback = c.get("bqsr.snp_mask.fallback_rows", 0)
    if route == "fallback":
        assert fallback == np.isin(contig_idx, [0, 1]).sum()
    else:
        assert fallback == 0


def test_mask_of_an_empty_batch():
    table = SnpTable({"chr1": [5, 9]})
    for shape in ((0, L), (4, 0)):
        got = table.mask_routed(NAMES, np.zeros(shape[0], np.int32),
                                np.zeros(shape, np.int64))
        assert got.mask.shape == shape and got.hit_rows == 0


@pytest.fixture(scope="module")
def wgs_ds(tmp_path_factory):
    from make_wgs_sam import make_wgs

    d = tmp_path_factory.mktemp("snp_mask")
    sam, vcf = str(d / "w.sam"), str(d / "w.vcf")
    make_wgs(sam, 2048, 100, n_contigs=2, contig_len=40_000,
             snp_every=400, known_sites_out=vcf)
    ds = load_alignments(sam)
    known = GenotypeDataset.load(
        vcf, contig_names=ds.seq_dict.names).snp_table()
    return ds, known


def test_mask_of_alignments_is_set_membership(wgs_ds):
    """Reference positions walked from real CIGARs (clips, indels,
    unmapped reads), masked with and without the alignment starts."""
    ds, known = wgs_ds
    b = ds.batch.to_numpy()
    ref_pos = cigar_ops.reference_positions_np(
        b.cigar_ops, b.cigar_lens, b.cigar_n, b.start, b.lmax)
    ci = np.asarray(b.contig_idx)
    want = _oracle(known, ds.seq_dict.names, ci, ref_pos)
    assert want.any()
    for start in (b.start, None):
        np.testing.assert_array_equal(
            known.mask_positions(ds.seq_dict.names, ci, ref_pos, start),
            want)


@pytest.mark.parametrize("backend", ["device", "numpy"])
def test_observe_times_the_mask_inside_its_prep(trace, wgs_ds, backend):
    ds, known = wgs_ds
    bq._observe_device(ds, known, backend)
    masks = [e for e in trace.events()
             if e["name"] == tele.SPAN_BQSR_SNP_MASK]
    assert len(masks) == 1
    assert masks[0]["parent"] == tele.SPAN_BQSR_OBSERVE_PREP
    assert trace.snapshot()["spans"][tele.SPAN_BQSR_OBSERVE_PREP][
        "count"] == 1
    c = trace.snapshot()["counters"]
    assert c["bqsr.snp_mask.rows"] == ds.batch.n_rows
    assert 0 < c["bqsr.snp_mask.hit_rows"] <= ds.batch.n_rows


def test_observe_without_known_snps_has_no_mask(trace, wgs_ds):
    ds, _ = wgs_ds
    bq._observe_device(ds, None, "numpy")
    snap = trace.snapshot()
    assert snap["spans"][tele.SPAN_BQSR_OBSERVE_PREP]["count"] == 1
    assert tele.SPAN_BQSR_SNP_MASK not in snap["spans"]
    assert not any(k.startswith("bqsr.snp_mask.") for k in snap["counters"])
