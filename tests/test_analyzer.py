"""Run analyzer (utils/analyzer.py): per-device attribution, barrier
decomposition, critical path, report rendering, CLI.

The load-bearing contract: on a trace with device-attributed span
tracks, ``busy_s + idle_s == wall_s`` per device (the smoke-test's
acceptance criterion asserts the same within 5% on a real 2-device
run), nested spans never double-count, replayed work is attributed as
``replay_s``, and the snapshot path reproduces the same totals from
``device_spans`` aggregates alone.
"""

import json

import pytest

from adam_tpu.utils import analyzer
from adam_tpu.utils import telemetry as tele

S = int(1e9)  # ns per second


def _synthetic_two_device_tracer():
    """A 10 s run with a KNOWN layout on two devices.

    device 0: dispatch [1, 3), fetch [5, 6)          -> busy 3, idle 7
    device 1: dispatch [2, 4), dispatch [4, 7)       -> busy 5, idle 5
              (the second dispatch nests a sub-span [4, 5) that must
              NOT double-count into busy)
    host:     pass A [0, 4), resolve [4, 5), merge-fetch [5, 6),
              solve [6, 7), pass C [7, 9), write wait [9, 10)
    """
    tr = tele.Tracer(recording=True)
    t0 = 0

    def add(name, start_s, dur_s, **attrs):
        tr.add_span(name, t0 + start_s * S, dur_s * S, **attrs)

    add(tele.SPAN_TOTAL, 0, 10)
    add(tele.SPAN_PASS_A, 0, 4)
    add(tele.SPAN_RESOLVE, 4, 1)
    add(tele.SPAN_OBS_MERGE, 5, 1)
    add(tele.SPAN_SOLVE, 6, 1)
    add(tele.SPAN_PASS_C, 7, 2)
    add(tele.SPAN_WRITE_WAIT, 9, 1)
    # device 0
    add(tele.SPAN_APPLY_DISPATCH, 1, 2, device=0, window=0)
    add(tele.SPAN_OBS_FETCH, 5, 1, device=0, window=0)
    # device 1 (with a nested sub-interval that must union away)
    add(tele.SPAN_APPLY_DISPATCH, 2, 2, device=1, window=1)
    add(tele.SPAN_APPLY_DISPATCH, 4, 3, device=1, window=3)
    add(tele.SPAN_BQSR_OBSERVE, 4, 1, device=1, window=3)
    return tr


def test_trace_attribution_sums_to_wall():
    tr = _synthetic_two_device_tracer()
    report = analyzer.analyze(tr.to_chrome_trace())
    assert report["kind"] == "trace"
    assert report["wall_s"] == pytest.approx(10.0)
    devs = report["devices"]
    assert set(devs) == {"0", "1"}
    d0, d1 = devs["0"], devs["1"]
    assert d0["busy_s"] == pytest.approx(3.0)
    assert d0["idle_s"] == pytest.approx(7.0)
    assert d0["fetch_s"] == pytest.approx(1.0)
    # nested/overlapping spans union, not sum: busy is 5, not 6
    assert d1["busy_s"] == pytest.approx(5.0)
    assert d1["idle_s"] == pytest.approx(5.0)
    # THE acceptance identity: busy + idle == wall, per device
    for d in devs.values():
        assert d["busy_s"] + d["idle_s"] == pytest.approx(report["wall_s"])
        assert not d["evicted"]


def test_trace_stage_decomposition_and_critical_path():
    tr = _synthetic_two_device_tracer()
    report = analyzer.analyze(tr.to_chrome_trace())
    stages = report["stages"]
    assert stages["pass_a_ingest"]["total_s"] == pytest.approx(4.0)
    assert stages["barrier1_resolve"]["total_s"] == pytest.approx(1.0)
    assert stages["barrier2_observe_fetch"]["total_s"] == pytest.approx(1.0)
    assert stages["write_tail"]["total_s"] == pytest.approx(1.0)
    assert stages["pass_a_ingest"]["frac"] == pytest.approx(0.4)
    cp = report["critical_path"]
    assert cp["edges"], "no critical-path edges"
    # the chain ends at the write tail and is bounded by the run wall
    assert cp["edges"][0]["edge_s"] <= report["wall_s"]
    names = {e["to"] for e in cp["edges"]} | {e["from"] for e in cp["edges"]}
    assert any(tele.SPAN_WRITE_WAIT in n for n in names)
    # window attribution survives into the edge labels
    assert any("[w" in n for n in names)
    # duration histograms are rebuilt from the events
    assert report["histograms"][tele.SPAN_APPLY_DISPATCH]["count"] == 3


def test_trace_replay_and_eviction_attribution():
    tr = tele.Tracer(recording=True)
    tr.add_span(tele.SPAN_TOTAL, 0, 10 * S)
    # device 1 worked [0, 2), then died; its replay umbrella spans [2, 5)
    tr.add_span(tele.SPAN_APPLY_DISPATCH, 0, 2 * S, device=1, window=0)
    tr.add_span(tele.SPAN_POOL_REPLAY, 2 * S, 3 * S, device=1, window=1)
    # the survivor re-ran window 1 inside that umbrella
    tr.add_span(tele.SPAN_APPLY_DISPATCH, 2 * S, 2 * S, device=0, window=1,
                replay=1)
    report = analyzer.analyze(tr.to_chrome_trace())
    d0, d1 = report["devices"]["0"], report["devices"]["1"]
    assert d1["evicted"] is True
    # pre-eviction work stays on the dead chip's row; the umbrella is
    # replay wall, not busy
    assert d1["busy_s"] == pytest.approx(2.0)
    assert d1["replay_s"] == pytest.approx(3.0)
    # the survivor's replayed work counts as ITS busy and replay
    assert d0["evicted"] is False
    assert d0["busy_s"] == pytest.approx(2.0)
    assert d0["replay_s"] == pytest.approx(2.0)


def test_snapshot_mode_matches_device_span_totals():
    tr = _synthetic_two_device_tracer()
    report = analyzer.analyze(tr.snapshot())
    assert report["kind"] == "snapshot"
    assert report["wall_s"] == pytest.approx(10.0)
    devs = report["devices"]
    assert devs["0"]["busy_s"] == pytest.approx(3.0)
    assert devs["0"]["fetch_s"] == pytest.approx(1.0)
    # aggregate mode SUMS (no timestamps): device 1's nested second = 6
    assert devs["1"]["busy_s"] == pytest.approx(6.0)
    # no event ring -> no critical path in snapshot mode
    assert "critical_path" not in report
    # survivor replay keys fold into replay_s
    tr2 = tele.Tracer(recording=True)
    tr2.add_span(tele.SPAN_TOTAL, 0, 4 * S)
    tr2.add_span(tele.SPAN_APPLY_DISPATCH, 0, 1 * S, device=0)
    tr2.add_span(tele.SPAN_APPLY_DISPATCH, 0, 2 * S, device=0, replay=1)
    devs2 = analyzer.analyze(tr2.snapshot())["devices"]
    assert devs2["0"]["busy_s"] == pytest.approx(3.0)
    assert devs2["0"]["replay_s"] == pytest.approx(2.0)


def test_utilization_from_snapshot_is_bench_embeddable():
    tr = _synthetic_two_device_tracer()
    util = analyzer.utilization_from_snapshot(tele.key_stable_snapshot(tr))
    assert util["wall_s"] == pytest.approx(10.0)
    assert set(util["devices"]) == {"0", "1"}
    # the device ledger rides along for the bench artifact (the
    # zero-filled key-stable snapshot yields hits/misses of 0)
    assert util["transfers"] == {}  # no transfers recorded in this run
    assert util["compiles"] == {}
    # the CPU-baseline shape: no device spans -> {} (key-stable)
    empty = analyzer.utilization_from_snapshot(
        tele.key_stable_snapshot(tele.Tracer(recording=True))
    )
    assert empty == {
        "wall_s": None, "devices": {}, "transfers": {}, "compiles": {},
    }


def test_render_report_and_document_kind(tmp_path):
    tr = _synthetic_two_device_tracer()
    text = analyzer.render_report(analyzer.analyze(tr.to_chrome_trace()))
    for needle in ("Per-device attribution", "Stage / barrier",
                   "Critical path", "busy_s"):
        assert needle in text, needle
    with pytest.raises(ValueError):
        analyzer.document_kind({"not": "an artifact"})
    # snapshot docs round-trip through disk (the --metrics-json shape)
    p = tmp_path / "m.json"
    p.write_text(json.dumps(tr.to_json()))
    report = analyzer.analyze_path(str(p))
    assert report["kind"] == "snapshot"


def test_analyze_cli_subcommand(tmp_path, capsys):
    from adam_tpu.cli.main import main

    tr = _synthetic_two_device_tracer()
    trace = tmp_path / "t.json"
    trace.write_text(json.dumps(tr.to_chrome_trace()))
    out_json = tmp_path / "a.json"
    rc = main(["analyze", str(trace), "-json", str(out_json)])
    assert rc == 0
    out = capsys.readouterr().out
    assert "Per-device attribution" in out
    doc = json.loads(out_json.read_text())
    assert doc["devices"]["0"]["busy_s"] == pytest.approx(3.0)
    # a non-artifact input is a clean usage error, not a traceback
    bogus = tmp_path / "bogus.json"
    bogus.write_text("{}")
    assert main(["analyze", str(bogus)]) == 2


def test_trace_mode_warns_on_ring_eviction():
    """A truncated flight recorder must not read as idle chips: the
    trace export carries the eviction count and the report surfaces
    it as a warning instead of silently fabricating idle time."""
    tr = tele.Tracer(recording=True, capacity=4)
    for i in range(10):
        tr.add_span(tele.SPAN_APPLY_DISPATCH, i * S, S, device=0, window=i)
    tr.add_span(tele.SPAN_TOTAL, 0, 10 * S)
    doc = tr.to_chrome_trace()
    assert doc["events_evicted"] == 7
    report = analyzer.analyze(doc)
    assert report["events_evicted"] == 7
    assert "WARNING" in analyzer.render_report(report)
    # an un-truncated run reports zero and no warning
    clean = analyzer.analyze(_synthetic_two_device_tracer().to_chrome_trace())
    assert clean["events_evicted"] == 0
    assert "WARNING" not in analyzer.render_report(clean)


def test_mirror_marker_prevents_twin_collapse():
    """Two genuinely-concurrent same-name same-timestamp spans on one
    device must BOTH count (the mirror dedup keys on the explicit cat
    marker, not timestamp coincidence)."""
    tr = tele.Tracer(recording=True)
    tr.add_span(tele.SPAN_TOTAL, 0, 10 * S)
    # identical (name, start, dur, device) twins from two worker threads
    tr.add_span(tele.SPAN_POOL_PREWARM_COMPILE, 0, 2 * S, thread="w0",
                device=0, kernel="k")
    tr.add_span(tele.SPAN_POOL_PREWARM_COMPILE, 0, 2 * S, thread="w1",
                device=0, kernel="k")
    report = analyzer.analyze(tr.to_chrome_trace())
    assert report["devices"]["0"]["n_spans"] == 2
    assert report["histograms"][tele.SPAN_POOL_PREWARM_COMPILE]["count"] == 2


# --------------------------------------------------------------------------
# device ledger sections + resumed-run snapshots
# --------------------------------------------------------------------------
def _resumed_run_snapshot():
    """Synthetic snapshot of a RESUMED 2-device run: this-process work
    only (the skipped windows never dispatched), resume counters set,
    and populated transfer/compile/HBM ledger sections."""
    tr = _synthetic_two_device_tracer()
    tr.count(tele.C_RESUME_WINDOWS_SKIPPED, 3)
    tr.count(tele.C_RESUME_HISTOGRAMS_LOADED, 2)
    tr.count(tele.C_READS_INGESTED, 10_000)
    with tele.pass_scope("observe"):
        tr.record_transfer("d2h", 2_000_000, 0.5, device="0")
        tr.record_transfer("d2h", 2_000_000, 0.25, device="1")
    with tele.pass_scope("apply"):
        tr.record_transfer("h2d", 8_000_000, 0.01, device="0")
    tr.record_compile("bqsr.observe", (1024, 128, 3), "cpu:1", 0.25,
                      in_window=True)
    tr.record_compile("bqsr.apply", (32768, 128, 3, 257), "cpu:0", 0.1,
                      in_window=False)
    tr.count(tele.C_COMPILE_HITS, 7)
    tr.record_hbm("0", 1 << 30, peak_bytes=2 << 30)
    return tr.snapshot()


def test_resumed_run_snapshot_report_renders_ledger_sections():
    """The satellite contract: a resumed run's snapshot analyzes with
    the resume counters surfaced, busy/idle attribution counting only
    this-process spans, and the transfer/compile/HBM sections rendering
    (in-window cold compiles flagged as warnings)."""
    snap = _resumed_run_snapshot()
    report = analyzer.analyze(snap)
    # resume counters present in the report's counter section
    assert report["counters"][tele.C_RESUME_WINDOWS_SKIPPED] == 3
    assert report["counters"][tele.C_RESUME_HISTOGRAMS_LOADED] == 2
    # busy/idle attribution is exactly the this-process span totals
    # (device 0: 2 s dispatch + 1 s fetch; nothing for skipped windows)
    assert report["devices"]["0"]["busy_s"] == pytest.approx(3.0)
    assert report["devices"]["0"]["idle_s"] == pytest.approx(7.0)
    # transfers: totals, per-device split, throughput, bytes-per-read
    xfer = report["transfers"]
    assert xfer["h2d_bytes"] == 8_000_000
    assert xfer["d2h_bytes"] == 4_000_000
    assert xfer["devices"]["0"]["d2h"]["bytes_per_s"] == 4_000_000
    assert xfer["devices"]["0"]["d2h"]["by_pass"] == {"observe": 2_000_000}
    assert xfer["bytes_per_read"] == pytest.approx(1200.0)
    # compile cache: the in-window miss is split out
    comp = report["compiles"]
    assert comp["cache_hits"] == 7 and comp["cache_misses"] == 2
    assert comp["prewarmed"] == 1
    assert [e["kernel"] for e in comp["in_window"]] == ["bqsr.observe"]
    # HBM peaks
    assert report["hbm"]["0"]["peak_bytes"] == 2 << 30
    text = analyzer.render_report(report)
    assert "Host<->device transfers" in text
    assert "WARNING: shapes cold-compiled INSIDE a timed window" in text
    assert "bqsr.observe[1024x128x3]" in text
    assert "HBM footprint" in text
    assert "resume.windows_skipped" in text


def test_hbm_unsupported_marker_when_devices_but_no_samples():
    """A device-attributed run whose backend lacks memory_stats must
    say so explicitly — never render zeros."""
    tr = _synthetic_two_device_tracer()
    report = analyzer.analyze(tr.snapshot())
    assert report["hbm"] == {"unsupported": True}
    assert "unsupported backend" in analyzer.render_report(report)
    # a host-only run (no device attribution) gets no HBM section
    host = tele.Tracer(recording=True)
    host.add_span(tele.SPAN_TOTAL, 0, S)
    host_report = analyzer.analyze(host.snapshot())
    assert host_report["hbm"] == {}


def test_trace_mode_carries_ledger_sections_too():
    """to_chrome_trace embeds transfers/compiles/hbm (+ counters), so
    trace-mode reports render the same ledger sections as snapshots."""
    snap_tr = tele.Tracer(recording=True)
    snap_tr.add_span(tele.SPAN_TOTAL, 0, 10 * S)
    snap_tr.add_span(tele.SPAN_APPLY_DISPATCH, S, S, device=0)
    snap_tr.record_transfer("h2d", 1_000_000, 0.001, device="0",
                            pass_name="apply")
    snap_tr.record_compile("markdup.columns", (4096, 32, 128), "cpu:0",
                           0.3, in_window=True)
    snap_tr.record_hbm("0", 123456)
    doc = snap_tr.to_chrome_trace()
    report = analyzer.analyze(doc)
    assert report["kind"] == "trace"
    assert report["transfers"]["h2d_bytes"] == 1_000_000
    assert len(report["compiles"]["in_window"]) == 1
    assert report["hbm"]["0"]["bytes_in_use"] == 123456
    assert report["counters"][tele.C_H2D_BYTES] == 1_000_000


def test_fused_megakernel_run_composes_incident_slo_perf_sections(
        tmp_path):
    """The satellite contract: a fused-megakernel run's artifact
    sitting next to incident bundles, an SLO budget, and a perf
    ledger analyzes into ONE report where every section composes —
    and per-device busy/idle attribution still sums exactly to the
    wall (new sections must not perturb the accounting)."""
    from adam_tpu.utils import incidents
    from adam_tpu.utils import perfledger
    from adam_tpu.utils import slo

    tr = _synthetic_two_device_tracer()
    # the megakernel tier's marks: fused B->C spans + tier decision
    tr.add_span(tele.SPAN_FUSED_BC, 1 * S, S, device=0, window=0)
    tr.gauge(tele.G_FUSED_BC, 1)
    tr.count(tele.C_FUSED_DISPATCHED, 2)
    snap = tr.snapshot()
    art = tmp_path / "m.json"
    art.write_text(json.dumps(snap))

    # sibling incident bundle
    incidents._reset_for_tests()
    incidents.install(str(tmp_path))
    try:
        incidents.maybe_record("slo.burn", trace_id="ab" * 8,
                               reason="budget burning at 25.0x")
    finally:
        incidents._reset_for_tests()
    # sibling SLO budget (self-contained: targets + cumulative counts)
    eng = slo.SLOEngine(
        slo.parse_slo_spec("t:p99(sched.job.run)<30s"), str(tmp_path))
    eng.observe_job("t", 1.0, ok=True)
    eng.observe_job("t", 99.0, ok=True)  # over the bound
    # sibling perf ledger, newest run regressed
    for i in range(4):
        perfledger.book(str(tmp_path),
                        {"spans.streamed.total.total_s": (10.0, "lower")},
                        run_id=f"r{i}")
    perfledger.book(str(tmp_path),
                    {"spans.streamed.total.total_s": (20.0, "lower")},
                    run_id="slow")

    report = analyzer.analyze_path(str(art))
    # every folded section present
    assert report["incidents"][0]["trigger"] == "slo.burn"
    slo_rep = report["slo"]
    assert slo_rep["objectives"][0]["compliance"] == pytest.approx(0.5)
    trend = report["perf_trend"]
    assert trend["n_runs"] == 5 and trend["runs_flagged"] == 1
    # and the accounting they ride along with is untouched:
    # busy + idle == wall per device, fused span counted as busy
    assert report["wall_s"] == pytest.approx(10.0)
    for dev in ("0", "1"):
        d = report["devices"][dev]
        assert d["busy_s"] + d["idle_s"] == \
            pytest.approx(report["wall_s"])
    assert report["devices"]["0"]["busy_s"] == pytest.approx(4.0)

    text = analyzer.render_report(report)
    for heading in ("Incidents (1 bundle(s))", "SLO", "Perf trend"):
        assert heading in text
    assert "slo.burn" in text
    assert "t:p99(sched.job.run)<30s" in text
