"""The realign tail, timed from the inside: the realigner's phase spans,
observe's host prep span and the BQSR work counters, through the CLI.

A small realign-and-BQSR streamed transform runs three ways — the
device backend on one device under the JAX profiler, the numpy backend,
the device backend fanned over four virtual devices — and the tests
read its ``--metrics-json`` and the host plane of its profile: the tail
is accounted for by its parts, every span lies on the profiler's host
plane, and the residue counters match a plain filter over the output
records and agree across backends and device counts."""

import json
import os
import sys

import numpy as np
import pytest

from adam_tpu.cli.main import main
from adam_tpu.io import context
from adam_tpu.utils import instrumentation as ins
from adam_tpu.utils import telemetry as tele

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))

N_READS = 8192
WINDOW = 2048

RUNS = {
    "device1": ("device", 1, True),
    "numpy1": ("numpy", 1, False),
    "device4": ("device", 4, False),
}


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    from make_wgs_sam import make_wgs

    d = tmp_path_factory.mktemp("tail")
    sam, vcf = str(d / "in.sam"), str(d / "known.vcf")
    make_wgs(sam, N_READS, 100, seed=5, n_contigs=2, contig_len=64_000,
             known_sites_out=vcf)
    rec = tele.TRACE.recording, ins.TIMERS.recording
    old = os.environ.get("ADAM_TPU_BQSR_BACKEND")
    out = {}
    try:
        for name, (backend, devices, profile) in RUNS.items():
            os.environ["ADAM_TPU_BQSR_BACKEND"] = backend
            tele.TRACE.reset()
            res = str(d / f"{name}.adam")
            mjson = str(d / f"{name}.json")
            argv = ["transform", sam, res, "-streaming",
                    "-mark_duplicate_reads", "-realign_indels",
                    "-recalibrate_base_qualities", "-known_snps", vcf,
                    "-window_reads", str(WINDOW), "--devices", str(devices),
                    "--metrics-json", mjson]
            xprof = str(d / f"{name}.xprof") if profile else None
            if xprof:
                argv += ["--xprof-dir", xprof]
            assert main(argv) == 0
            out[name] = dict(snap=json.load(open(mjson)), out=res,
                             xprof=xprof)
    finally:
        if old is None:
            os.environ.pop("ADAM_TPU_BQSR_BACKEND", None)
        else:
            os.environ["ADAM_TPU_BQSR_BACKEND"] = old
        tele.TRACE.recording, ins.TIMERS.recording = rec
        tele.TRACE.reset()
    return out


def _host_events(xprof_dir) -> dict:
    from jax.profiler import ProfileData

    paths = []
    for root, _dirs, files in os.walk(xprof_dir):
        paths += [os.path.join(root, f) for f in files
                  if f.endswith(".xplane.pb")]
    counts: dict = {}
    for plane in ProfileData.from_file(sorted(paths)[-1]).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    counts[ev.name] = counts.get(ev.name, 0) + 1
    return counts


def test_program_spans_on_the_profiler_host_plane(runs):
    r = runs["device1"]
    got = _host_events(r["xprof"])
    windows = r["snap"]["counters"]["windows.ingested"]
    assert windows == N_READS // WINDOW
    # one prep per observed window plus one for the realigned part
    assert got[tele.SPAN_BQSR_OBSERVE_PREP] == windows + 1
    assert r["snap"]["spans"][tele.SPAN_BQSR_OBSERVE_PREP]["count"] == (
        windows + 1)
    for name in tele.REALIGN_PHASE_SPANS:
        assert got.get(name) == 1, name
    for name in (tele.SPAN_TAIL, tele.SPAN_OBSERVE,
                 tele.SPAN_OBSERVE_REALIGNED):
        assert got.get(name) == 1, name


@pytest.mark.parametrize("run", sorted(RUNS))
def test_snp_mask_inside_every_prep(runs, run):
    """Every observe call masks its known SNPs once, every row of it —
    the windows' and the realigned part's.  The windows of the sorted
    input take the window-local site map; the realigned part's few rows
    span both contigs, more bases than their N x L positions hold, so
    they take the searchsorted route."""
    snap = runs[run]["snap"]
    spans, c = snap["spans"], snap["counters"]
    assert spans[tele.SPAN_BQSR_SNP_MASK]["count"] == (
        spans[tele.SPAN_BQSR_OBSERVE_PREP]["count"])
    assert c["bqsr.snp_mask.rows"] == N_READS + c["realign.candidate_rows"]
    assert 0 < c["bqsr.snp_mask.hit_rows"] < N_READS
    assert c["bqsr.snp_mask.fallback_rows"] == c["realign.candidate_rows"]
    if runs[run]["xprof"]:
        got = _host_events(runs[run]["xprof"])
        assert got[tele.SPAN_BQSR_SNP_MASK] == got[
            tele.SPAN_BQSR_OBSERVE_PREP]


@pytest.mark.parametrize("run", sorted(RUNS))
def test_tail_is_accounted_for_by_its_parts(runs, run):
    spans = runs[run]["snap"]["spans"]
    tail = spans[tele.SPAN_TAIL]["total_s"]
    parts = sum(spans[n]["total_s"] for n in (
        *tele.REALIGN_PHASE_SPANS, tele.SPAN_OBSERVE,
        tele.SPAN_OBSERVE_REALIGNED))
    assert all(n in spans for n in tele.REALIGN_PHASE_SPANS)
    assert 0 <= tail - parts <= 0.10 * tail


def _output(run):
    return context.load_alignments(run["out"]).batch.to_numpy()


def test_observe_residues_match_the_plain_filter(runs):
    """The reads observe admits, recomputed from the output records:
    primary, mapped, not a duplicate, passed QC, with qualities, MAPQ
    in (0, 255), MD present."""
    from adam_tpu.formats.strings import StringColumn

    r = runs["numpy1"]
    ds = context.load_alignments(r["out"])
    b = ds.batch.to_numpy()
    flags = np.asarray(b.flags).astype(np.int64)
    mapq = np.asarray(b.mapq)
    ok = (
        np.asarray(b.valid)
        & ((flags & (0x4 | 0x100 | 0x200 | 0x400 | 0x800)) == 0)
        & np.asarray(b.has_qual)
        & (mapq > 0) & (mapq != 255)
        & StringColumn.of(ds.sidecar.md).valid[: b.n_rows]
    )
    want = int(np.asarray(b.lengths)[ok].sum())
    assert 0 < want
    assert r["snap"]["counters"]["bqsr.observe.residues"] == want


def test_apply_residues_are_every_read_with_qualities(runs):
    r = runs["numpy1"]
    b = _output(r)
    keep = np.asarray(b.valid) & np.asarray(b.has_qual)
    assert r["snap"]["counters"]["bqsr.apply.residues"] == int(
        np.asarray(b.lengths)[keep].sum())


@pytest.mark.parametrize("counter", ["bqsr.observe.residues",
                                     "bqsr.apply.residues"])
def test_residue_counters_agree_across_backends_and_devices(runs, counter):
    got = {name: r["snap"]["counters"][counter] for name, r in runs.items()}
    assert len(set(got.values())) == 1, got
    assert runs["device4"]["snap"]["gauges"]["device.pool.devices"][
        "last"] == 4
