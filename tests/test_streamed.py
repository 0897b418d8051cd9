"""Streamed overlapped transform: equality with the monolithic path.

The pipeline contract (pipelines/streamed.py) is that window edges are
invisible: duplicate groups, BQSR statistics and realignment targets
that span two ingest windows must resolve exactly as in one batch — the
same boundary-correctness the sharded path needs
(rdd/read/MarkDuplicates.scala:66-128, GenomicPartitioners.scala:63-85).
"""

import os
import sys

import numpy as np
import pytest

from adam_tpu.api.datasets import AlignmentDataset
from adam_tpu.formats import schema
from adam_tpu.formats.strings import StringColumn
from adam_tpu.io import context
from adam_tpu.pipelines.streamed import transform_streamed

sys.path.insert(
    0, os.path.join(os.path.dirname(__file__), "..", "tools")
)


def _row_table(ds):
    """Window-order-independent view: rows keyed by (name, flags)."""
    d = ds.compact()
    b = d.batch.to_numpy()
    names = StringColumn.of(d.sidecar.names).to_fixed_bytes().astype("S64")
    order = np.lexsort((np.asarray(b.flags), names))
    cols = {
        f: np.asarray(getattr(b, f))[order]
        for f in ["flags", "start", "end", "mapq", "lengths", "contig_idx",
                  "cigar_n"]
    }
    cols["names"] = names[order]
    L = b.lmax
    cols["quals"] = np.asarray(b.quals)[order][:, :L]
    side = d.sidecar
    cols["md"] = [side.md[i] for i in order]
    cols["attrs"] = [side.attrs[i] for i in order]
    cols["oq"] = [side.orig_quals[i] for i in order]
    return cols


def _assert_equal(mono, streamed):
    a, b = _row_table(mono), _row_table(streamed)
    np.testing.assert_array_equal(a["names"], b["names"])
    for f in ["flags", "start", "end", "mapq", "lengths", "contig_idx",
              "cigar_n"]:
        np.testing.assert_array_equal(a[f], b[f], err_msg=f)
    L = min(a["quals"].shape[1], b["quals"].shape[1])
    np.testing.assert_array_equal(a["quals"][:, :L], b["quals"][:, :L])
    assert a["md"] == b["md"]
    assert a["attrs"] == b["attrs"]
    assert a["oq"] == b["oq"]


def test_streamed_matches_monolithic(tmp_path):
    """8 windows of a synthetic WGS-shaped file: identical output rows
    (flags incl. duplicate marks, recalibrated quals, realigned cigars,
    MD/OQ/attrs) vs load-then-stage-by-stage."""
    from make_synth_sam import make_sam

    path = str(tmp_path / "in.sam")
    make_sam(path, 8192, 100)
    mono = (
        context.load_alignments(path)
        .mark_duplicates()
        .realign_indels()
        .recalibrate_base_qualities()
    )
    out = str(tmp_path / "out.adam")
    stats = transform_streamed(path, out, window_reads=1024)
    assert stats["n_reads"] == 8192
    back = context.load_alignments(out)
    _assert_equal(mono, back)


def test_streamed_boundary_duplicates_and_targets(tmp_path):
    """Duplicate groups and an indel target engineered to straddle a
    window edge (window_reads=8): the global resolves must see them
    whole."""
    from adam_tpu.io.sam import SamHeader, write_sam
    from adam_tpu.formats.batch import pack_reads
    from adam_tpu.models.dictionaries import (
        RecordGroup, RecordGroupDictionary, SequenceDictionary,
        SequenceRecord,
    )

    sd = SequenceDictionary((SequenceRecord("chr1", 100000),))
    rgd = RecordGroupDictionary((RecordGroup("rg1", library="lib1"),))
    recs = []
    # 12 duplicate fragments at one position -> rows 0..11 span windows
    # 0 and 1 (window_reads=8); the winner (highest quality) is in
    # window 1, so cross-window score comparison is exercised
    for i in range(12):
        phred = 30 if i == 9 else 20
        recs.append(dict(
            name=f"frag{i}", flags=0, contig_idx=0, start=500, mapq=60,
            cigar="20M", seq="ACGTACGTACGTACGTACGT", qual=chr(33 + phred) * 20,
            read_group_idx=0, attrs="MD:Z:20",
        ))
    # an insertion-carrying read just before the window-2 edge plus
    # overlapping mismatch-free reads after the edge: a realignment
    # target whose reads live in two windows
    recs.append(dict(
        name="indel", flags=0, contig_idx=0, start=600, mapq=60,
        cigar="10M2I8M", seq="AAAAAAAAAACCAAAAAAAA", qual="I" * 20,
        read_group_idx=0, attrs="MD:Z:18",
    ))
    for i in range(8):
        recs.append(dict(
            name=f"cover{i}", flags=0, contig_idx=0, start=598 + i, mapq=60,
            cigar="20M", seq="A" * 20, qual="I" * 20,
            read_group_idx=0, attrs="MD:Z:20",
        ))
    batch, side = pack_reads(recs)
    header = SamHeader(seq_dict=sd, read_groups=rgd)
    path = str(tmp_path / "in.sam")
    write_sam(path, batch, side, header)

    mono = (
        context.load_alignments(path)
        .mark_duplicates()
        .realign_indels()
        .recalibrate_base_qualities()
    )
    out = str(tmp_path / "out.adam")
    transform_streamed(path, out, window_reads=8)
    back = context.load_alignments(out)
    _assert_equal(mono, back)

    # sanity on the duplicate semantics themselves: exactly 11 of the 12
    # fragments marked, winner unmarked
    b = back.compact()
    bb = b.batch.to_numpy()
    dup = (np.asarray(bb.flags) & schema.FLAG_DUPLICATE) != 0
    marks = {b.sidecar.names[i]: bool(dup[i]) for i in range(bb.n_rows)}
    assert not marks["frag9"]
    assert sum(marks[f"frag{i}"] for i in range(12)) == 11


def test_streamed_stage_toggles(tmp_path):
    """Each stage can be disabled independently (the CLI flag set)."""
    from make_synth_sam import make_sam

    path = str(tmp_path / "in.sam")
    make_sam(path, 2048, 100)
    mono = context.load_alignments(path).mark_duplicates()
    out = str(tmp_path / "out.adam")
    transform_streamed(
        path, out, window_reads=512, recalibrate=False, realign=False
    )
    back = context.load_alignments(out)
    _assert_equal(mono, back)


# ---------------------------------------------------------------------------
# Durable window-granular resume (docs/ROBUSTNESS.md; --run-dir/--resume)
# ---------------------------------------------------------------------------
def _parts_hash(out_dir):
    import hashlib

    return {
        f: hashlib.sha256(
            open(os.path.join(out_dir, f), "rb").read()
        ).hexdigest()
        for f in os.listdir(out_dir) if f.startswith("part-")
    }


def test_streamed_journal_resume_skips_completed_windows(tmp_path):
    """A journaled run resumes: a full resume skips every window, a
    resume after two parts vanish rewrites exactly those two —
    byte-identical to the journal-free run either way."""
    from make_synth_sam import make_sam

    path = str(tmp_path / "in.sam")
    make_sam(path, 2048, 100)
    clean = str(tmp_path / "clean.adam")
    transform_streamed(path, clean, window_reads=256)
    baseline = _parts_hash(clean)

    out, rd = str(tmp_path / "j.adam"), str(tmp_path / "run")
    s1 = transform_streamed(path, out, window_reads=256, run_dir=rd)
    assert s1["windows_resumed"] == 0
    assert _parts_hash(out) == baseline
    # the journal artifacts exist: window map + obs sidecars + table
    assert os.path.isfile(os.path.join(rd, "JOURNAL.json"))
    assert os.path.isfile(os.path.join(rd, "table.npz"))
    assert os.listdir(os.path.join(rd, "obs"))

    s2 = transform_streamed(path, out, window_reads=256, run_dir=rd,
                            resume=True)
    assert s2["windows_fresh"] == 0 and s2["windows_resumed"] > 0
    assert _parts_hash(out) == baseline

    # journaled-but-deleted parts degrade to "incomplete", never a hole
    os.unlink(os.path.join(out, "part-r-00001.parquet"))
    os.unlink(os.path.join(out, "part-r-00004.parquet"))
    s3 = transform_streamed(path, out, window_reads=256, run_dir=rd,
                            resume=True)
    assert s3["windows_fresh"] == 2
    assert _parts_hash(out) == baseline


_KILL_DRIVER = (
    "import sys\n"
    "from adam_tpu.pipelines.streamed import transform_streamed\n"
    "transform_streamed(sys.argv[1], sys.argv[2], window_reads=256,\n"
    "                   run_dir=sys.argv[3], resume=sys.argv[4] == '1')\n"
)

#: (phase, arrival offset) — one SIGKILL at each phase boundary the
#: proc.kill fault point exposes (docs/ROBUSTNESS.md)
_KILL_MATRIX = [
    ("ingest", 3), ("pass_a", 4), ("pass_b", 2), ("barrier2", 0),
    ("pass_c", 2), ("write", 1),
]


@pytest.mark.parametrize("phase,after", _KILL_MATRIX,
                         ids=[p for p, _ in _KILL_MATRIX])
def test_streamed_sigkill_then_resume_bit_identical(
    tmp_path_factory, kill_resume_input, phase, after
):
    """SIGKILL (a real host death via the proc.kill fault point) at
    each phase boundary, then --resume: the completed output must be
    byte-identical to the uninterrupted run."""
    import signal
    import subprocess

    path, baseline = kill_resume_input
    d = tmp_path_factory.mktemp(f"kill_{phase}")
    out, rd = str(d / "out.adam"), str(d / "run")
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        # host backend: the machinery under test is the journal, and a
        # subprocess chip probe would only slow both runs down
        "ADAM_TPU_BQSR_BACKEND": "numpy",
        "ADAM_TPU_FAULTS":
            f"proc.kill=kill,device={phase},after={after},times=1",
    })
    cwd = os.path.join(os.path.dirname(__file__), "..")
    rc = subprocess.run(
        [sys.executable, "-c", _KILL_DRIVER, path, out, rd, "0"],
        env=env, cwd=cwd,
    ).returncode
    assert rc == -signal.SIGKILL, f"{phase}: expected SIGKILL, got {rc}"
    env.pop("ADAM_TPU_FAULTS")
    rc = subprocess.run(
        [sys.executable, "-c", _KILL_DRIVER, path, out, rd, "1"],
        env=env, cwd=cwd,
    ).returncode
    assert rc == 0, f"{phase}: resume exited {rc}"
    assert _parts_hash(out) == baseline, f"{phase}: resumed output differs"
    # crash consistency held throughout: no staging residue
    assert not [f for f in os.listdir(out) if f.endswith(".tmp")]
    assert not os.path.isdir(os.path.join(out, "_temporary"))


@pytest.fixture(scope="module")
def kill_resume_input(tmp_path_factory):
    """Shared input + uninterrupted-run baseline for the SIGKILL matrix
    (one numpy-backend run, matching the subprocess drivers)."""
    from make_synth_sam import make_sam

    d = tmp_path_factory.mktemp("kill_resume")
    path = str(d / "in.sam")
    make_sam(path, 2048, 100)
    clean = str(d / "clean.adam")
    os.environ["ADAM_TPU_BQSR_BACKEND"] = "numpy"
    try:
        transform_streamed(path, clean, window_reads=256)
    finally:
        os.environ.pop("ADAM_TPU_BQSR_BACKEND", None)
    return path, _parts_hash(clean)


def test_streamed_resume_refuses_changed_input_and_flags(tmp_path):
    """A resume whose input bytes or flag composition differ from the
    journal's fingerprint restarts clean — the output must equal a
    fresh run of the NEW configuration, with no stale parts mixed in."""
    from make_synth_sam import make_sam

    pA, pB = str(tmp_path / "a.sam"), str(tmp_path / "b.sam")
    make_sam(pA, 1024, 100)
    make_sam(pB, 1536, 100)
    out, rd = str(tmp_path / "out.adam"), str(tmp_path / "run")
    transform_streamed(pA, out, window_reads=256, run_dir=rd)

    # changed input content: refused, restarted, equals clean run of B
    s = transform_streamed(pB, out, window_reads=256, run_dir=rd,
                           resume=True)
    assert s["windows_resumed"] == 0
    clean_b = str(tmp_path / "cleanB.adam")
    transform_streamed(pB, clean_b, window_reads=256)
    assert _parts_hash(out) == _parts_hash(clean_b)

    # changed window plan: refused again (the part layout would differ)
    s = transform_streamed(pB, out, window_reads=512, run_dir=rd,
                           resume=True)
    assert s["windows_resumed"] == 0
    # changed stage composition: ditto
    s = transform_streamed(pB, out, window_reads=512, run_dir=rd,
                           resume=True, realign=False)
    assert s["windows_resumed"] == 0


def test_streamed_resume_tolerates_torn_journal(tmp_path):
    """A corrupt/torn journal (crashed writer, disk hiccup) costs a
    clean restart, not a crash and never trust."""
    from make_synth_sam import make_sam

    path = str(tmp_path / "in.sam")
    make_sam(path, 1024, 100)
    out, rd = str(tmp_path / "out.adam"), str(tmp_path / "run")
    transform_streamed(path, out, window_reads=256, run_dir=rd)
    baseline = _parts_hash(out)
    with open(os.path.join(rd, "JOURNAL.json"), "w") as fh:
        fh.write('{"schema": "adam_tpu.run_journal/1", "windows": TORN')
    s = transform_streamed(path, out, window_reads=256, run_dir=rd,
                           resume=True)
    assert s["windows_resumed"] == 0 and s["windows_fresh"] > 0
    assert _parts_hash(out) == baseline


def test_streamed_tuning_flags_and_dump_observations(tmp_path):
    """The realign tuning knobs thread through (a prohibitive LOD
    threshold suppresses all realignment) and -dump_observations writes
    the merged observation table CSV."""
    from make_wgs_sam import make_wgs

    path = str(tmp_path / "in.sam")
    make_wgs(path, 2048, 100, n_contigs=1, contig_len=30_000)
    obs = str(tmp_path / "obs.csv")
    out1 = str(tmp_path / "strict.adam")
    transform_streamed(
        path, out1, window_reads=512,
        lod_threshold=1e12, dump_observations=obs,
    )
    assert open(obs).read().startswith("ReadGroup,")
    strict = context.load_alignments(out1)
    b1 = strict.batch.to_numpy()
    # nothing clears the absurd LOD bar: no read gets the +10 mapq
    base_mapq = int(np.asarray(b1.mapq)[np.asarray(b1.valid)].max())
    out2 = str(tmp_path / "default.adam")
    transform_streamed(path, out2, window_reads=512)
    b2 = context.load_alignments(out2).batch.to_numpy()
    assert int(np.asarray(b2.mapq).max()) == base_mapq + 10  # default realigns
    assert int(np.asarray(b1.mapq).max()) == base_mapq
