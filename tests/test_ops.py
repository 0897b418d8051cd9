import numpy as np
import pytest
import jax.numpy as jnp

from adam_tpu.formats import schema
from adam_tpu.formats.batch import pack_reads
from adam_tpu.ops import cigar as cigar_ops
from adam_tpu.ops import flagstat as fs
from adam_tpu.ops import kmer as kmer_ops
from adam_tpu.ops import phred
from adam_tpu.ops import smith_waterman as sw
from adam_tpu.ops.mdtag import MdTag, batch_md_arrays


# ------------------------------------------------------------------- phred
def test_phred_tables():
    np.testing.assert_allclose(
        np.asarray(phred.phred_to_error_probability(np.array([0, 10, 20]))),
        [1.0, 0.1, 0.01],
    )
    assert int(phred.error_probability_to_phred(0.001)) == 30
    assert int(phred.success_probability_to_phred(0.999)) == 30
    # reference rounding rule: math.round(-10*log10(p))
    assert int(phred.error_probability_to_phred(0.0005)) == 33


# ------------------------------------------------------------------- cigar
def _cig_batch(cigs, starts):
    recs = [
        dict(name=f"r{i}", flags=0, contig_idx=0, start=s, mapq=60, cigar=c,
             seq="A" * schema.cigar_str_stats(c)[0], qual=None)
        for i, (c, s) in enumerate(zip(cigs, starts))
    ]
    b, _ = pack_reads(recs)
    return b.to_device()


def test_cigar_walks():
    b = _cig_batch(["10M", "2S8M", "3M2I3M2D2M", "2H4M3S"], [100, 100, 100, 100])
    rl = np.asarray(cigar_ops.reference_length(b.cigar_ops, b.cigar_lens, b.cigar_n))
    np.testing.assert_array_equal(rl, [10, 8, 10, 4])
    ql = np.asarray(cigar_ops.query_length(b.cigar_ops, b.cigar_lens, b.cigar_n))
    np.testing.assert_array_equal(ql, [10, 10, 10, 7])
    lead = np.asarray(cigar_ops.leading_clip(b.cigar_ops, b.cigar_lens, b.cigar_n))
    np.testing.assert_array_equal(lead, [0, 2, 0, 2])
    trail = np.asarray(cigar_ops.trailing_clip(b.cigar_ops, b.cigar_lens, b.cigar_n))
    np.testing.assert_array_equal(trail, [0, 0, 0, 3])
    us = np.asarray(cigar_ops.unclipped_start(b.start, b.cigar_ops, b.cigar_lens, b.cigar_n))
    np.testing.assert_array_equal(us, [100, 98, 100, 98])


def test_five_prime_position():
    # forward read: unclipped start; reverse: exclusive unclipped end
    b = _cig_batch(["2S8M", "2S8M"], [100, 100])
    flags = np.array([0, schema.FLAG_REVERSE], np.int32)
    fp = np.asarray(
        cigar_ops.five_prime_position(
            b.start, b.end, flags, b.cigar_ops, b.cigar_lens, b.cigar_n
        )
    )
    np.testing.assert_array_equal(fp, [98, 108])


def test_reference_positions():
    b = _cig_batch(["2S3M2D3M", "3M2I1M"], [10, 50])
    rp = np.asarray(
        cigar_ops.reference_positions(
            b.cigar_ops, b.cigar_lens, b.cigar_n, b.start, b.lmax
        )
    )
    np.testing.assert_array_equal(rp[0, :8], [-1, -1, 10, 11, 12, 15, 16, 17])
    np.testing.assert_array_equal(rp[1, :6], [50, 51, 52, -1, -1, 53])


# ---------------------------------------------------------------- flagstat
def test_flagstat_small(ref_resources):
    from adam_tpu.io import load_alignments

    ds = load_alignments(str(ref_resources / "small.sam"))
    failed, passed = ds.flagstat()
    assert passed.total == 20
    assert failed.total == 0
    assert passed.mapped == 20
    assert passed.paired_in_sequencing == 0
    out = fs.format_flagstat(failed, passed)
    assert "20 + 0 in total" in out
    assert "20 + 0 mapped (100.00%:0.00%)" in out


def test_flagstat_paired_flags():
    P, M, U = schema.FLAG_PAIRED, schema.FLAG_MATE_UNMAPPED, schema.FLAG_UNMAPPED
    recs = [
        dict(name="a", flags=P | 0x40 | 0x2, contig_idx=0, start=10, mapq=60,
             cigar="4M", seq="ACGT", qual="IIII", mate_contig_idx=1, mate_start=50),
        dict(name="b", flags=P | 0x80, contig_idx=1, start=50, mapq=3,
             cigar="4M", seq="ACGT", qual="IIII", mate_contig_idx=0, mate_start=10),
        dict(name="c", flags=P | M, contig_idx=0, start=20, mapq=60,
             cigar="4M", seq="ACGT", qual="IIII"),
        dict(name="d", flags=U | 0x200, contig_idx=-1, start=-1, mapq=0,
             cigar="*", seq="ACGT", qual="IIII"),
        dict(name="e", flags=schema.FLAG_DUPLICATE, contig_idx=0, start=30,
             mapq=60, cigar="4M", seq="ACGT", qual="IIII", mate_contig_idx=-1),
    ]
    b, _ = pack_reads(recs)
    failed, passed = fs.flagstat(b)
    assert passed.total == 4 and failed.total == 1
    assert passed.read1 == 1 and passed.read2 == 1
    assert passed.properly_paired == 1
    assert passed.singleton == 1  # read c: paired, mapped, mate unmapped
    assert passed.with_mate_mapped_to_diff_chromosome == 2  # a and b
    assert passed.with_mate_mapped_to_diff_chromosome_mapq5 == 1  # only a
    assert passed.duplicates_primary.total == 1
    assert passed.duplicates_primary.cross_chromosome == 1  # mate contig -1 != 0
    assert failed.mapped == 0


# ------------------------------------------------------------------- kmers
def test_count_kmers_simple():
    recs = [
        dict(name="a", flags=4, contig_idx=-1, start=-1, mapq=255, cigar="*",
             seq="ACGTACGT", qual="I" * 8),
        dict(name="b", flags=4, contig_idx=-1, start=-1, mapq=255, cigar="*",
             seq="ACGTA", qual="I" * 5),
    ]
    b, _ = pack_reads(recs)
    counts = kmer_ops.count_kmers(b, 4)
    # brute force
    expect = {}
    for s in ["ACGTACGT", "ACGTA"]:
        for i in range(len(s) - 3):
            expect[s[i : i + 4]] = expect.get(s[i : i + 4], 0) + 1
    assert counts == expect


def test_count_kmers_with_n():
    recs = [
        dict(name="a", flags=4, contig_idx=-1, start=-1, mapq=255, cigar="*",
             seq="ACNTA", qual="IIIII"),
    ]
    b, _ = pack_reads(recs)
    counts = kmer_ops.count_kmers(b, 3)
    assert counts == {"ACN": 1, "CNT": 1, "NTA": 1}


def test_count_kmers_matches_reference_example(ref_resources):
    """k-mer counts over reads12.sam equal a pure-python sliding count."""
    from adam_tpu.io import load_alignments

    ds = load_alignments(str(ref_resources / "reads12.sam"))
    counts = ds.count_kmers(21)
    b = ds.batch.to_numpy()
    expect: dict[str, int] = {}
    for i in range(b.n_rows):
        if not b.valid[i]:
            continue
        s = schema.decode_bases(b.bases[i], int(b.lengths[i]))
        for j in range(len(s) - 20):
            w = s[j : j + 21]
            expect[w] = expect.get(w, 0) + 1
    assert counts == expect


def test_count_kmers_empty_batch():
    from adam_tpu.formats.batch import ReadBatch

    assert kmer_ops.count_kmers(ReadBatch.empty(0, 10, 2), 4) == {}
    assert kmer_ops.count_qmers(ReadBatch.empty(0, 10, 2), 4) == {}


def test_mdtag_iupac_bases():
    tag = MdTag.parse("5R10", 0)
    assert tag.mismatches == {5: "R"}
    assert tag.to_string() == "5R10"


def test_count_qmers():
    recs = [
        dict(name="a", flags=4, contig_idx=-1, start=-1, mapq=255, cigar="*",
             seq="ACGT", qual="II5I"),
    ]
    b, _ = pack_reads(recs)
    q = kmer_ops.count_qmers(b, 2)
    p40 = 1 - 10 ** -4.0
    p20 = 1 - 10 ** -2.0
    assert set(q) == {"AC", "CG", "GT"}
    np.testing.assert_allclose(q["AC"], p40 * p40, rtol=1e-12)
    np.testing.assert_allclose(q["CG"], p40 * p20, rtol=1e-12)
    np.testing.assert_allclose(q["GT"], p20 * p40, rtol=1e-12)


# ---------------------------------------------------------- smith-waterman
# End-to-end vectors from the reference's SmithWatermanSuite (:180-220).
def test_sw_simple():
    a = sw.smith_waterman("AAAA", "AAAA", 1.0, 0.0, -1.0, -1.0)
    assert a.cigar_x == "4M" and a.cigar_y == "4M"
    assert a.score == 4.0


def test_sw_indel():
    a = sw.smith_waterman("ACATGA", "ACGA", 1.0, 0.0, -0.333, -0.333)
    assert a.cigar_x == "2M2I2M"
    assert a.cigar_y == "2M2D2M"


def test_sw_snp_long():
    x = "ATTAGACTACTTAATATACAGATTTACCCCAATAGA"
    y = "ATTAGACTACTTAATATACAGAATTACCCCAATAGA"
    a = sw.smith_waterman(x, y, 1.0, 0.0, -0.333, -0.333)
    assert a.cigar_x == "36M" and a.cigar_y == "36M"


def test_sw_short_indel_long():
    x = "ATTAGACTACTTAATATACAGATTTACCCCAATAGA"
    y = "ATTAGACTACTTAATATACAGATACCCCAATAGA"
    a = sw.smith_waterman(x, y, 1.0, 0.0, -0.333, -0.333)
    assert a.cigar_x == "22M2I12M"
    assert a.cigar_y == "22M2D12M"


def test_sw_containment():
    x = "ATTAGACTACTTAATATACAGATTTACCCCAATAGA"
    y = "ACTTAATATACAGATTTACC"
    a = sw.smith_waterman(x, y, 1.0, 0.0, -0.333, -0.333)
    assert a.cigar_x == "20M"
    assert a.x_start == 8
    assert a.y_start == 0


def test_sw_batch_padded():
    """Batched alignment with different lengths under one jit shape."""
    xs = ["AAAA", "ACATGA"]
    ys = ["AAAA", "ACGA"]
    lx = max(len(s) for s in xs)
    ly = max(len(s) for s in ys)
    xc = np.stack([
        np.pad(schema.encode_bases(s), (0, lx - len(s)), constant_values=schema.BASE_PAD)
        for s in xs
    ])
    yc = np.stack([
        np.pad(schema.encode_bases(s), (0, ly - len(s)), constant_values=schema.BASE_PAD)
        for s in ys
    ])
    res = sw.smith_waterman_batch(
        xc, np.array([4, 6]), yc, np.array([4, 4]), 1.0, 0.0, -0.333, -0.333
    )
    assert res[0].cigar_x == "4M"
    assert res[1].cigar_x == "2M2I2M"


def test_sw_score_only_parity():
    """The striped score-only fills (the GCUPS path) agree with the
    trackback fill's best scores bit-for-bit — scan and Pallas
    (interpret) backends, padded variable lengths."""
    import jax.numpy as jnp

    rng = np.random.default_rng(7)
    B, lx, ly = 24, 31, 45
    xc = rng.integers(0, 4, (B, lx)).astype(np.int32)
    yc = rng.integers(0, 4, (B, ly)).astype(np.int32)
    xl = rng.integers(4, lx + 1, B).astype(np.int32)
    yl = rng.integers(4, ly + 1, B).astype(np.int32)
    args = (1.0, -0.333, -0.5, -0.5)
    _, bs, _ = sw._sw_fill_scan_best(
        jnp.asarray(xc), jnp.asarray(xl), jnp.asarray(yc), jnp.asarray(yl),
        *args, lx, ly,
    )
    ref = np.asarray(bs).max(axis=1)
    got_scan = np.asarray(sw.sw_best_scores(xc, xl, yc, yl, *args,
                                            backend="scan"))
    np.testing.assert_array_equal(ref, got_scan)
    got_pl = np.asarray(
        sw._sw_score_pallas(
            jnp.asarray(xc), jnp.asarray(xl), jnp.asarray(yc),
            jnp.asarray(yl), lx, ly, *args, interpret=True,
        )
    )
    np.testing.assert_array_equal(ref, got_pl)


def test_sw_score_long_reads_multi_tile():
    """Long-read shapes: lx past one 128-lane tile (L=256 sublane
    state, 9-step delete chains) agrees across backends, N codes
    included.  (Multi-grid-tile batches run on the real chip in
    benchmark_gcups; interpret mode keeps this test single-tile.)"""
    rng = np.random.default_rng(11)
    B, lx, ly = 300, 250, 310
    xc = rng.integers(0, 5, (B, lx)).astype(np.int32)  # incl. N codes
    yc = rng.integers(0, 5, (B, ly)).astype(np.int32)
    xl = rng.integers(40, lx + 1, B).astype(np.int32)
    yl = rng.integers(60, ly + 1, B).astype(np.int32)
    args = (1.0, -0.333, -0.5, -0.5)
    got_scan = np.asarray(sw.sw_best_scores(xc, xl, yc, yl, *args,
                                            backend="scan"))
    got_pl = np.asarray(
        sw._sw_score_pallas(
            jnp.asarray(xc), jnp.asarray(xl), jnp.asarray(yc),
            jnp.asarray(yl), lx, ly, *args, interpret=True,
        )
    )
    np.testing.assert_array_equal(got_scan, got_pl)


# ------------------------------------------------------------------ mdtag
def test_mdtag_parse_and_tostring_roundtrip():
    for md in ["75", "10A5", "0A74", "10^AC5", "5A0C5", "0C0C10", "10^AC0T5"]:
        tag = MdTag.parse(md, 100)
        assert tag.to_string() == md, md


def test_mdtag_parse_structure():
    tag = MdTag.parse("10A5^GG3", 0)
    assert tag.is_match(5) and not tag.is_match(10)
    assert tag.mismatches == {10: "A"}
    assert tag.deletions == {16: "G", 17: "G"}
    assert tag.end() == 20


def test_mdtag_from_alignment():
    #       read:  ACGTACGT  ref: ACGAACGT -> mismatch at pos 3
    tag = MdTag.from_alignment("ACGTACGT", "ACGAACGT", "8M", 0)
    assert tag.to_string() == "3A4"
    # deletion: read ACGTGT vs ref ACGTAAGT cigar 4M2D2M
    tag = MdTag.from_alignment("ACGTGT", "ACGTAAGT", "4M2D2M", 0)
    assert tag.to_string() == "4^AA2"
    # insertion consumes read only
    tag = MdTag.from_alignment("ACGTTTGT", "ACGTGT", "4M2I2M", 0)
    assert tag.to_string() == "6"


def test_mdtag_get_reference():
    tag = MdTag.parse("4^AA2", 10)
    assert tag.get_reference("ACGTGT", "4M2D2M") == "ACGTAAGT"
    tag = MdTag.parse("3A4", 0)
    assert tag.get_reference("ACGTACGT", "8M") == "ACGAACGT"
    # corrupt alignment: CIGAR span overruns the read -> loud failure,
    # not a silently truncated reference
    with pytest.raises(IndexError):
        MdTag.parse("12", 0).get_reference("ACGT", "12M")


def test_mdtag_move_alignment():
    # realign same read against a shifted reference
    tag = MdTag.move_alignment("ACGTAAGT", "ACGTGT", "4M2D2M", 50)
    assert tag.to_string() == "4^AA2"
    assert tag.start == 50


def test_batch_md_arrays():
    recs = [
        dict(name="a", flags=0, contig_idx=0, start=10, mapq=60, cigar="4M",
             seq="ACGT", qual="IIII", md="2G1"),
        dict(name="b", flags=0, contig_idx=0, start=20, mapq=60, cigar="2M2I2M",
             seq="ACTTGT", qual="IIIIII", md="4"),
    ]
    b, side = pack_reads(recs)
    is_mm, ref_codes, has_md = batch_md_arrays(b, side)
    np.testing.assert_array_equal(is_mm[0, :4], [False, False, True, False])
    assert schema.decode_bases(ref_codes[0], 4) == "ACGT".replace("G", "G")[:2] + "G" + "T"
    # insertion positions have no reference base
    np.testing.assert_array_equal(ref_codes[1, 2:4], [schema.BASE_PAD] * 2)
    assert has_md.all()


def test_batch_md_arrays_matches_oracle():
    """Differential: vectorized MD path == per-read oracle on tricky MDs."""
    import numpy as np

    from adam_tpu.formats.batch import pack_reads
    from adam_tpu.ops.mdtag import batch_md_arrays_reference

    rng = np.random.default_rng(7)
    recs = []
    cases = [
        ("4M", "ACGT", "4"),                    # all match
        ("4M", "ACGT", "0A3"),                  # leading 0 run
        ("4M", "ACGT", "3A0"),                  # trailing 0 run
        ("4M", "ACGT", "1A0C1"),                # adjacent mismatches
        ("2M2D2M", "ACGT", "2^TT2"),            # deletion
        ("2S4M", "TTACGT", "2A1"),              # soft clip
        ("2M3I2M", "ACTTTGT", "1G2"),           # insertion
        ("1S2M1D2M1S", "AACGTC", "2^G0A1"),     # everything at once
        ("6M", "ACGTAC", "0A0C0G0T0A0C0"),      # all mismatch
    ]
    for i, (cig, seq, md) in enumerate(cases):
        recs.append(dict(name=f"r{i}", flags=0, contig_idx=0, start=10 + i,
                         mapq=60, cigar=cig, seq=seq, qual="I" * len(seq),
                         md=md))
    # plus random simple reads, some without MD
    for i in range(40):
        L = int(rng.integers(3, 12))
        seq = "".join(rng.choice(list("ACGT"), L))
        md = None if i % 5 == 0 else str(L)
        recs.append(dict(name=f"q{i}", flags=0, contig_idx=0, start=i,
                         mapq=60, cigar=f"{L}M", seq=seq, qual="I" * L,
                         md=md))
    b, side = pack_reads(recs)
    got = batch_md_arrays(b, side)
    want = batch_md_arrays_reference(b, side)
    np.testing.assert_array_equal(got[0], want[0])
    np.testing.assert_array_equal(got[1], want[1])
    np.testing.assert_array_equal(got[2], want[2])


def test_batch_md_arrays_empty_batch():
    from adam_tpu.formats.batch import ReadBatch, ReadSidecar

    b = ReadBatch.empty()
    is_mm, ref_codes, has_md = batch_md_arrays(b, ReadSidecar())
    assert is_mm.shape[0] == 0 and has_md.shape[0] == 0


def test_sw_pallas_interpret_parity():
    """The Pallas wavefront kernel must produce the scan fill's scores and
    moves bit-for-bit (interpret mode runs the kernel on CPU)."""
    rng = np.random.default_rng(7)
    B, lx, ly = 9, 37, 29
    xc = rng.integers(0, 4, (B, lx)).astype(np.int32)
    yc = rng.integers(0, 4, (B, ly)).astype(np.int32)
    xl = rng.integers(1, lx + 1, B).astype(np.int32)
    yl = rng.integers(1, ly + 1, B).astype(np.int32)
    args = (1.0, -0.333, -0.5, -0.5)

    m_scan, bs_scan, bd_scan = sw._sw_fill_scan_best(
        jnp.asarray(xc), jnp.asarray(xl), jnp.asarray(yc), jnp.asarray(yl),
        *args, lx, ly,
    )
    m_pl, bs_pl, bd_pl = sw._sw_fill_pallas(
        jnp.asarray(xc), jnp.asarray(xl), jnp.asarray(yc), jnp.asarray(yl),
        lx, ly, *args, interpret=True,
    )
    np.testing.assert_array_equal(np.asarray(m_pl), np.asarray(m_scan))
    np.testing.assert_array_equal(np.asarray(bs_pl), np.asarray(bs_scan))
    # the winning diagonal only matters where a real (finite) best exists
    finite = np.isfinite(np.asarray(bs_scan))
    np.testing.assert_array_equal(
        np.asarray(bd_pl)[finite], np.asarray(bd_scan)[finite]
    )
