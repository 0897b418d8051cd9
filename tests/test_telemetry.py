"""Telemetry layer: spans, counters, gauges, flight recorder, exports.

The contract under test (utils/telemetry.py, docs/OBSERVABILITY.md):
recording is lossless under concurrent writers (the ``TimerRegistry``
lock discipline), the flight recorder is bounded and evicts oldest
first, the JSON / Chrome-trace exports round-trip, and the streamed
pipeline's ``stats`` dict is a pure derived view of its span data —
recomputing the view from an exported snapshot reproduces it exactly.
"""

import json
import os
import re
import sys
import threading

import pytest

from adam_tpu.utils import instrumentation as ins
from adam_tpu.utils import telemetry as tele

sys.path.insert(
    0, os.path.join(os.path.dirname(__file__), "..", "tools")
)


@pytest.fixture(autouse=True)
def _clean_global_state():
    """Tests toggle the process-wide TRACE/TIMERS; leave them as found."""
    rec_t, rec_i = tele.TRACE.recording, ins.TIMERS.recording
    yield
    tele.TRACE.recording = rec_t
    ins.TIMERS.recording = rec_i
    tele.TRACE.reset()
    ins.TIMERS.recording = True
    ins.TIMERS.reset()
    ins.TIMERS.recording = rec_i


# --------------------------------------------------------------------------
# core recorder
# --------------------------------------------------------------------------
def test_disabled_tracer_records_nothing():
    tr = tele.Tracer(recording=False)
    with tr.span(tele.SPAN_TOKENIZE, window=0):
        pass
    tr.count(tele.C_READS_INGESTED, 100)
    tr.gauge(tele.G_POOL_DEPTH, 3)
    snap = tr.snapshot()
    assert snap["spans"] == {} and snap["counters"] == {}
    assert snap["gauges"] == {} and snap["events_recorded"] == 0
    # the disabled fast path hands back one shared no-op object
    assert tr.span(tele.SPAN_SOLVE) is tr.span(tele.SPAN_TOKENIZE)


def test_concurrent_recording_is_lossless():
    """≥4 threads hammering spans+counters+gauges: nothing lost."""
    tr = tele.Tracer(recording=True, capacity=1 << 16)
    n_threads, per_thread = 8, 500
    barrier = threading.Barrier(n_threads)

    def worker(tid):
        barrier.wait()
        for i in range(per_thread):
            with tr.span(tele.SPAN_TOKENIZE, window=i):
                pass
            tr.count(tele.C_READS_INGESTED, 2)
            tr.gauge(tele.G_POOL_DEPTH, tid)

    threads = [
        threading.Thread(target=worker, args=(t,)) for t in range(n_threads)
    ]
    for t in threads:
        t.start()
    # concurrent readers must not race the writers (satellite: locked
    # snapshot) — exercise while recording is in flight
    for _ in range(50):
        tr.snapshot()
        tr.span_seconds()
    for t in threads:
        t.join()
    snap = tr.snapshot()
    total = n_threads * per_thread
    assert snap["spans"][tele.SPAN_TOKENIZE]["count"] == total
    assert snap["counters"][tele.C_READS_INGESTED] == 2 * total
    assert snap["gauges"][tele.G_POOL_DEPTH]["n"] == total
    assert snap["gauges"][tele.G_POOL_DEPTH]["min"] == 0
    assert snap["gauges"][tele.G_POOL_DEPTH]["max"] == n_threads - 1
    assert snap["events_recorded"] == total
    assert snap["events_evicted"] == 0


def test_ring_buffer_evicts_oldest_keeps_newest():
    tr = tele.Tracer(recording=True, capacity=16)
    t0 = 1_000_000
    for i in range(100):
        tr.add_span(tele.SPAN_TOKENIZE, t0 + i, 10, window=i)
    evs = tr.events()
    assert len(evs) == 16
    # newest 16 survive, oldest first within the ring
    assert [e["args"]["window"] for e in evs] == list(range(84, 100))
    snap = tr.snapshot()
    assert snap["events_recorded"] == 100
    assert snap["events_retained"] == 16
    assert snap["events_evicted"] == 84
    # aggregates live OUTSIDE the ring: totals stay exact post-eviction
    assert snap["spans"][tele.SPAN_TOKENIZE]["count"] == 100


def test_span_nesting_records_parent_and_thread():
    tr = tele.Tracer(recording=True)
    with tr.span(tele.SPAN_PASS_C):
        with tr.span(tele.SPAN_APPLY_DISPATCH, window=3):
            pass
    evs = tr.events()
    # inner exits (and records) first
    assert [e["name"] for e in evs] == [
        tele.SPAN_APPLY_DISPATCH, tele.SPAN_PASS_C,
    ]
    assert evs[0]["parent"] == tele.SPAN_PASS_C
    assert evs[0]["args"]["window"] == 3
    assert "parent" not in evs[1]
    assert evs[0]["thread"] == threading.current_thread().name


def test_absorb_merges_aggregates_and_events():
    a = tele.Tracer(recording=True)
    b = tele.Tracer(recording=True)
    for tr, k in ((a, 1), (b, 2)):
        for _ in range(k):
            with tr.span(tele.SPAN_SOLVE):
                pass
        tr.count(tele.C_PARTS_WRITTEN, k)
        tr.gauge(tele.G_DEVICE_INFLIGHT, k)
    a.absorb(b)
    snap = a.snapshot()
    assert snap["spans"][tele.SPAN_SOLVE]["count"] == 3
    assert snap["counters"][tele.C_PARTS_WRITTEN] == 3
    g = snap["gauges"][tele.G_DEVICE_INFLIGHT]
    assert (g["min"], g["max"], g["n"], g["last"]) == (1, 2, 2, 2)
    assert snap["events_recorded"] == 3


# --------------------------------------------------------------------------
# exports round-trip
# --------------------------------------------------------------------------
def _populated_tracer():
    tr = tele.Tracer(recording=True)
    with tr.span(tele.SPAN_PASS_A):
        with tr.span(tele.SPAN_TOKENIZE, window=0):
            pass
    tr.count(tele.C_WINDOWS_INGESTED)
    tr.gauge(tele.G_POOL_DEPTH, 2)
    return tr


def test_json_export_round_trips(tmp_path):
    tr = _populated_tracer()
    ins.TIMERS.recording = True
    ins.TIMERS.reset()
    ins.TIMERS.add(ins.SAM_ENCODE, 2_000_000_000)
    p = str(tmp_path / "m.json")
    tr.dump_json(p, include_events=True)
    doc = json.load(open(p))
    assert doc["meta"]["schema"] == "adam_tpu.telemetry/1"
    # the snapshot sections survive the file round-trip verbatim
    snap = tr.snapshot()
    assert doc["spans"] == snap["spans"]
    assert doc["counters"] == snap["counters"]
    assert doc["gauges"] == snap["gauges"]
    # the timers section is the TimerRegistry snapshot, same rows the
    # printed table carries
    assert doc["timers"][ins.SAM_ENCODE] == {"count": 1, "total_s": 2.0}
    # include_events carries the flight recorder
    assert [e["name"] for e in doc["events"]] == [
        e["name"] for e in tr.events()
    ]


def test_chrome_trace_export_loads_and_tracks_threads(tmp_path):
    tr = _populated_tracer()

    def other_thread():
        with tr.span(tele.SPAN_PART_ENCODE, rows=8):
            pass

    t = threading.Thread(target=other_thread, name="pw-enc-0")
    t.start()
    t.join()
    p = str(tmp_path / "t.json")
    tr.dump_chrome_trace(p)
    doc = json.load(open(p))
    evs = doc["traceEvents"]
    meta = [e for e in evs if e["ph"] == "M"]
    spans = [e for e in evs if e["ph"] == "X"]
    # one thread_name metadata record per recording thread, distinct tids
    names = {e["args"]["name"] for e in meta}
    assert "pw-enc-0" in names and len(names) == 2
    assert len({e["tid"] for e in meta}) == 2
    # complete events carry microsecond ts/dur on the right track
    by_name = {e["name"]: e for e in spans}
    assert set(by_name) == {
        tele.SPAN_PASS_A, tele.SPAN_TOKENIZE, tele.SPAN_PART_ENCODE,
    }
    ring = {e["name"]: e for e in tr.events()}
    for name, ev in by_name.items():
        assert ev["dur"] == pytest.approx(ring[name]["dur_ns"] / 1e3)
    enc_tid = by_name[tele.SPAN_PART_ENCODE]["tid"]
    tok_tid = by_name[tele.SPAN_TOKENIZE]["tid"]
    assert enc_tid != tok_tid
    # nesting attribution survives as args.parent
    assert by_name[tele.SPAN_TOKENIZE]["args"]["parent"] == tele.SPAN_PASS_A


def test_key_stable_snapshot_zero_fills_device_metrics():
    tr = tele.Tracer(recording=True)
    tr.count(tele.C_READS_INGESTED, 5)
    snap = tele.key_stable_snapshot(tr)
    for name in tele.DEVICE_ONLY_COUNTERS:
        assert snap["counters"][name] == 0
    for name in tele.DEVICE_ONLY_GAUGES:
        assert snap["gauges"][name] == {
            "last": 0, "min": 0, "max": 0, "n": 0,
        }
    # real values are never clobbered by the zero-fill
    assert snap["counters"][tele.C_READS_INGESTED] == 5


def test_merge_snapshots_reports_per_host_skew():
    def host(total_s):
        tr = tele.Tracer(recording=True)
        tr.add_span(tele.SPAN_PASS_A, 0, int(total_s * 1e9))
        return tr.snapshot()

    merged = tele.merge_snapshots([host(1.0), host(3.0)])
    assert merged["n_hosts"] == 2
    sk = merged["span_skew"][tele.SPAN_PASS_A]
    assert sk["min_s"] == pytest.approx(1.0)
    assert sk["max_s"] == pytest.approx(3.0)


# --------------------------------------------------------------------------
# TimerRegistry satellites
# --------------------------------------------------------------------------
def test_timer_snapshot_safe_during_recording():
    reg = ins.TimerRegistry(recording=True)
    stop = threading.Event()

    def hammer(i):
        while not stop.is_set():
            with reg.time(f"t{i}"):
                pass

    threads = [
        threading.Thread(target=hammer, args=(i,)) for i in range(4)
    ]
    for t in threads:
        t.start()
    try:
        for _ in range(200):
            snap = reg.snapshot()
            for name, (count, total_ns) in snap.items():
                assert count >= 1 and total_ns >= 0
            reg.report()
    finally:
        stop.set()
        for t in threads:
            t.join()
    assert set(reg.snapshot()) == {f"t{i}" for i in range(4)}


def test_timers_reset_clears_telemetry_metrics():
    ins.TIMERS.recording = True
    tele.TRACE.recording = True
    ins.TIMERS.add(ins.SAM_ENCODE, 1000)
    tele.TRACE.count(tele.C_PARTS_WRITTEN, 7)
    tele.TRACE.gauge(tele.G_POOL_DEPTH, 4)
    ins.TIMERS.reset()
    assert ins.TIMERS.snapshot() == {}
    snap = tele.TRACE.snapshot()
    # one reset clears the whole metrics surface (satellite 1)
    assert snap["counters"] == {} and snap["gauges"] == {}


def test_private_registry_reset_leaves_global_telemetry_alone():
    tele.TRACE.recording = True
    tele.TRACE.count(tele.C_PARTS_WRITTEN, 3)
    reg = ins.TimerRegistry(recording=True)
    reg.add(ins.SAM_ENCODE, 1000)
    reg.reset()
    assert reg.snapshot() == {}
    # only the process-global TIMERS reset cascades into TRACE
    assert tele.TRACE.snapshot()["counters"][tele.C_PARTS_WRITTEN] == 3


def test_device_trace_reentrant_noop(tmp_path, caplog, monkeypatch):
    """A second concurrent device_trace warns and no-ops instead of
    crashing the profiler (satellite 2)."""
    monkeypatch.setattr(ins, "_DEVICE_TRACE_ACTIVE", True)
    with caplog.at_level("WARNING", logger="adam_tpu.utils.instrumentation"):
        with ins.device_trace(str(tmp_path / "xprof")):
            pass
    assert any("already active" in r.message for r in caplog.records)
    # the no-op inner exit must NOT release the outer trace's guard
    assert ins._DEVICE_TRACE_ACTIVE is True


# --------------------------------------------------------------------------
# streamed pipeline: stats is a derived view of the span data
# --------------------------------------------------------------------------
def test_streamed_stats_equals_span_view(tmp_path):
    """Smoke run of the streamed flagship (CPU): the returned ``stats``
    timing keys must be exactly reproducible from the exported global
    snapshot via streamed_stats_view — the dict IS the view."""
    from adam_tpu.pipelines.streamed import transform_streamed
    from make_synth_sam import make_sam

    path = str(tmp_path / "in.sam")
    make_sam(path, 2048, 100)
    tele.TRACE.reset()
    tele.TRACE.recording = True
    try:
        stats = transform_streamed(
            path, str(tmp_path / "out.adam"), window_reads=512
        )
    finally:
        tele.TRACE.recording = False
    snap = tele.TRACE.snapshot()
    view = tele.streamed_stats_view(snap)
    assert view, "span view is empty — stage spans were not recorded"
    for key, want in view.items():
        assert stats[key] == want, key
    # every stage wall the old hand-maintained dict carried is present
    for key in ("ingest_pass_s", "resolve_s", "split_s", "observe_s",
                "solve_s", "realign_s", "apply_split_s", "write_wait_s",
                "total_s"):
        assert key in view, key
    # counters sanity: every read and window accounted for
    assert snap["counters"][tele.C_READS_INGESTED] == 2048
    assert snap["counters"][tele.C_WINDOWS_INGESTED] == 4
    assert snap["counters"][tele.C_PARTS_WRITTEN] >= 1
    assert snap["counters"][tele.C_BYTES_WRITTEN] > 0
    # the writer pool's submit-gate gauge saw real depth samples
    assert snap["gauges"][tele.G_POOL_DEPTH]["n"] >= 2
    assert snap["gauges"][tele.G_POOL_DEPTH]["max"] >= 1
    # per-window tokenize spans landed on the ingest thread's track
    tok = snap["spans"].get(tele.SPAN_TOKENIZE)
    assert tok and tok["count"] >= 4


def test_cli_metrics_json_and_trace_out(tmp_path, capsys):
    """Acceptance: transform with -print_metrics --metrics-json
    --trace-out yields a counters/gauges table under the timer table, a
    JSON snapshot whose per-stage walls match the printed rows, and a
    Chrome trace with overlapping stage spans on distinct tracks."""
    from adam_tpu.cli.main import main
    from make_synth_sam import make_sam

    sam = str(tmp_path / "in.sam")
    make_sam(sam, 1024, 100)
    mj = str(tmp_path / "m.json")
    to = str(tmp_path / "t.json")
    rc = main([
        "transform", sam, str(tmp_path / "out.adam"), "-streaming",
        "-mark_duplicate_reads", "-print_metrics",
        "--metrics-json", mj, "--trace-out", to,
    ])
    assert rc == 0
    out = capsys.readouterr().out
    assert "Timings" in out and "Counters" in out
    doc = json.load(open(mj))
    # the printed timer rows and the JSON timers section are the same
    # data: every printed (name, count, total) reappears in the JSON
    lines = out.splitlines()
    start = lines.index("=======") + 2  # skip header row
    n_rows = 0
    for line in lines[start:]:
        if not line.strip():
            break
        m = re.fullmatch(r"(.+?)\s+(\d+)\s+(\d+\.\d{3})", line)
        assert m, line
        name, count, total = m.groups()
        row = doc["timers"][name]
        assert row["count"] == int(count)
        assert round(row["total_s"], 3) == float(total)
        n_rows += 1
    assert n_rows >= 3
    assert doc["counters"][tele.C_READS_INGESTED] == 1024
    # the Chrome trace is loadable and shows the overlap: ingest-thread
    # tokenize spans and main-thread stage spans on distinct tracks
    trace = json.load(open(to))
    spans = [e for e in trace["traceEvents"] if e["ph"] == "X"]
    names = {e["name"] for e in spans}
    assert tele.SPAN_TOKENIZE in names and tele.SPAN_PASS_A in names
    tok_tids = {e["tid"] for e in spans if e["name"] == tele.SPAN_TOKENIZE}
    ing_tids = {e["tid"] for e in spans if e["name"] == tele.SPAN_PASS_A}
    assert tok_tids and ing_tids and not (tok_tids & ing_tids)


# --------------------------------------------------------------------------
# histograms: fixed log-spaced buckets, quantiles, merges
# --------------------------------------------------------------------------
def test_histogram_bucket_edges_are_fixed_and_log_spaced():
    """Bucket i spans [10^(i/4), 10^((i+1)/4)) — global, data-independent
    edges, and every observed value lands in exactly its bucket."""
    for v in (1e-6, 0.001, 0.5, 1.0, 3.7, 42.0, 1e4):
        idx = tele.hist_bucket_index(v)
        lo, hi = tele.hist_bucket_bounds(idx)
        assert lo <= v < hi, (v, idx, lo, hi)
    # adjacent buckets tile the line with ratio 10^(1/4)
    lo0, hi0 = tele.hist_bucket_bounds(0)
    lo1, hi1 = tele.hist_bucket_bounds(1)
    assert hi0 == pytest.approx(lo1)
    assert hi0 / lo0 == pytest.approx(10 ** 0.25)
    # nonpositive values clamp into the lowest bucket instead of NaN-ing
    assert tele.hist_bucket_index(0.0) == tele.hist_bucket_index(-5.0)


def test_histogram_observe_and_quantiles():
    tr = tele.Tracer(recording=True)
    for v in [0.001] * 90 + [1.0] * 9 + [10.0]:
        tr.observe(tele.H_FETCH_SECONDS, v)
    h = tr.snapshot()["histograms"][tele.H_FETCH_SECONDS]
    assert h["count"] == 100
    assert h["min"] == 0.001 and h["max"] == 10.0
    assert h["sum"] == pytest.approx(0.09 + 9.0 + 10.0)
    # p50 sits in the 1ms bucket, p99 in the 1s bucket (bucket-midpoint
    # estimates: within one bucket ratio of the true value)
    assert h["p50"] == pytest.approx(0.001, rel=1.0)
    assert 0.5 <= h["p99"] <= 2.0
    # the max observation is only reachable at the very top quantile
    assert h["p99"] < h["max"]


def test_histogram_merge_is_associative():
    def hist(values):
        tr = tele.Tracer(recording=True)
        for v in values:
            tr.observe(tele.H_FETCH_SECONDS, v)
        return tr.snapshot()["histograms"][tele.H_FETCH_SECONDS]

    a = hist([0.001, 0.002, 0.004])
    b = hist([1.0, 2.0])
    c = hist([50.0, 0.0005])
    left = tele.merge_histograms(tele.merge_histograms(a, b), c)
    right = tele.merge_histograms(a, tele.merge_histograms(b, c))
    assert left == right
    assert left["count"] == 7
    assert left["min"] == 0.0005 and left["max"] == 50.0
    # merging with an empty histogram is the identity
    assert tele.merge_histograms(a, {}) == tele.merge_histograms({}, a)


def test_observe_concurrent_is_lossless():
    """≥8 threads hammering observe(): nothing lost, bounds exact."""
    tr = tele.Tracer(recording=True)
    n_threads, per_thread = 8, 400
    barrier = threading.Barrier(n_threads)

    def worker(tid):
        barrier.wait()
        for i in range(per_thread):
            tr.observe(tele.H_POOL_SUBMIT_WAIT, 0.001 * (tid + 1))

    threads = [
        threading.Thread(target=worker, args=(t,)) for t in range(n_threads)
    ]
    for t in threads:
        t.start()
    for _ in range(50):  # concurrent readers must not race the writers
        tr.snapshot()
    for t in threads:
        t.join()
    h = tr.snapshot()["histograms"][tele.H_POOL_SUBMIT_WAIT]
    assert h["count"] == n_threads * per_thread
    assert h["min"] == pytest.approx(0.001)
    assert h["max"] == pytest.approx(0.008)
    assert sum(h["buckets"].values()) == h["count"]


def test_spans_get_automatic_duration_histograms():
    tr = tele.Tracer(recording=True)
    tr.add_span(tele.SPAN_SOLVE, 0, int(0.25 * 1e9))
    tr.add_span(tele.SPAN_SOLVE, 0, int(0.5 * 1e9))
    snap = tr.snapshot()
    h = snap["histograms"][tele.SPAN_SOLVE]
    assert h["count"] == snap["spans"][tele.SPAN_SOLVE]["count"] == 2
    assert h["min"] == pytest.approx(0.25)
    assert h["max"] == pytest.approx(0.5)
    # disabled tracers record no histograms at all
    off = tele.Tracer(recording=False)
    off.observe(tele.H_FETCH_SECONDS, 1.0)
    assert off.snapshot()["histograms"] == {}


def test_absorb_and_merge_snapshots_carry_histograms():
    a = tele.Tracer(recording=True)
    b = tele.Tracer(recording=True)
    a.observe(tele.H_FETCH_SECONDS, 0.1)
    b.observe(tele.H_FETCH_SECONDS, 10.0)
    sa, sb = a.snapshot(), b.snapshot()
    a.absorb(b)
    h = a.snapshot()["histograms"][tele.H_FETCH_SECONDS]
    assert h["count"] == 2 and h["min"] == 0.1 and h["max"] == 10.0
    merged = tele.merge_snapshots([sa, sb])
    mh = merged["histograms"][tele.H_FETCH_SECONDS]
    assert mh["count"] == 2 and mh["min"] == 0.1 and mh["max"] == 10.0


def test_key_stable_snapshot_zero_fills_histograms():
    tr = tele.Tracer(recording=True)
    snap = tele.key_stable_snapshot(tr)
    for name in tele.DEVICE_ONLY_HISTOGRAMS:
        h = snap["histograms"][name]
        assert h["count"] == 0 and h["p50"] is None


def test_report_prints_histogram_quantiles():
    tr = tele.Tracer(recording=True)
    tr.observe(tele.H_FETCH_SECONDS, 0.5)
    text = tr.report()
    assert "Histograms" in text
    assert tele.H_FETCH_SECONDS in text


# --------------------------------------------------------------------------
# device_spans: replayed work never conflates with organic occupancy
# --------------------------------------------------------------------------
def test_device_spans_separate_replayed_from_organic_work():
    """The eviction-attribution fix: an evicted device's pre-eviction
    spans stay under its original key, and the windows a survivor
    re-runs for it aggregate under `<survivor>:replay` — never summed
    into the survivor's own row."""
    tr = tele.Tracer(recording=True)
    # pre-eviction: devices 0 and 1 each do organic work
    tr.add_span(tele.SPAN_APPLY_DISPATCH, 0, int(1e9), device=0)
    tr.add_span(tele.SPAN_APPLY_DISPATCH, 0, int(2e9), device=1)
    # device 1 dies; its window replays on device 0 with the replay attr
    tr.add_span(tele.SPAN_POOL_REPLAY, 0, int(3e9), device=1)
    tr.add_span(tele.SPAN_APPLY_DISPATCH, 0, int(4e9), device=0, replay=1)
    dev = tr.snapshot()["device_spans"]
    disp = dev[tele.SPAN_APPLY_DISPATCH]
    # organic rows untouched by the replay
    assert disp["0"] == {"count": 1, "total_s": pytest.approx(1.0)}
    assert disp["1"] == {"count": 1, "total_s": pytest.approx(2.0)}
    # replayed work lands under the survivor's :replay key
    assert disp["0:replay"] == {"count": 1, "total_s": pytest.approx(4.0)}
    # the umbrella stays attributed to the FAILED chip
    assert dev[tele.SPAN_POOL_REPLAY]["1"]["total_s"] == pytest.approx(3.0)
    # cascading eviction: a device dying MID-replay records its own
    # umbrella inside the outer replay scope (replay=1 attr), which is
    # exempt from the :replay rewrite — recovery wall must stay under
    # the failed chip's plain key or the analyzer counts it as busy
    # and misses the eviction
    tr.add_span(tele.SPAN_POOL_REPLAY, 0, int(1e9), device=0, replay=1)
    dev2 = tr.snapshot()["device_spans"][tele.SPAN_POOL_REPLAY]
    assert "0:replay" not in dev2
    assert dev2["0"]["total_s"] == pytest.approx(1.0)


# --------------------------------------------------------------------------
# heartbeat
# --------------------------------------------------------------------------
def test_progress_sink_from_env(monkeypatch):
    monkeypatch.delenv("ADAM_TPU_PROGRESS", raising=False)
    assert tele.progress_sink_from_env() is None
    monkeypatch.setenv("ADAM_TPU_PROGRESS", "0")
    assert tele.progress_sink_from_env() is None
    for raw in ("1", "stderr", "-"):
        monkeypatch.setenv("ADAM_TPU_PROGRESS", raw)
        assert tele.progress_sink_from_env() == "stderr"
    monkeypatch.setenv("ADAM_TPU_PROGRESS", "/tmp/hb.ndjson")
    assert tele.progress_sink_from_env() == "/tmp/hb.ndjson"
    monkeypatch.setenv("ADAM_TPU_PROGRESS_INTERVAL_S", "bogus")
    assert tele.progress_interval_s() == pytest.approx(2.0)
    monkeypatch.setenv("ADAM_TPU_PROGRESS_INTERVAL_S", "0.25")
    assert tele.progress_interval_s() == pytest.approx(0.25)


def test_heartbeat_ndjson_schema_is_stable(tmp_path):
    """Every emitted line carries exactly HEARTBEAT_FIELDS, in order;
    the final line is done=true; counters sum across the sampled
    tracers (run tracer + global TRACE, as the streamed wiring does)."""
    tr = tele.Tracer(recording=True)
    other = tele.Tracer(recording=True)
    tr.count(tele.C_WINDOWS_INGESTED, 3)
    tr.count(tele.C_READS_INGESTED, 3000)
    tr.count(tele.C_RESUME_WINDOWS_SKIPPED, 2)
    other.count(tele.C_PARTS_WRITTEN, 2)
    other.count(tele.C_BYTES_WRITTEN, 12345)
    p = str(tmp_path / "hb.ndjson")
    hb = tele.Heartbeat([tr, other], sink=p, interval_s=0.05)
    hb.set_total(4)
    hb.set_provider(lambda: {"inflight_per_device": {"0": 2, "1": 1}})
    hb.start()
    import time as _time

    _time.sleep(0.2)
    hb.stop()
    hb.stop()  # idempotent
    lines = [json.loads(l) for l in open(p)]
    assert len(lines) >= 3  # start line + >=1 periodic + final
    for l in lines:
        assert tuple(l.keys()) == tele.HEARTBEAT_FIELDS
        assert l["schema"] == tele.HEARTBEAT_SCHEMA
    last = lines[-1]
    assert last["done"] is True
    assert last["windows_ingested"] == 3
    assert last["reads_ingested"] == 3000
    assert last["parts_written"] == 2
    assert last["bytes_written"] == 12345
    assert last["windows_total"] == 4
    # resumed-vs-fresh visibility: the resume.windows_skipped counter
    # surfaces as the windows_resumed field (0 on fresh runs)
    assert last["windows_resumed"] == 2
    assert last["inflight_per_device"] == {"0": 2, "1": 1}
    assert last["eta_s"] is not None  # 2 of 4 parts -> extrapolable
    assert [l["seq"] for l in lines] == list(range(len(lines)))
    # a broken provider must not kill the beat
    hb2 = tele.Heartbeat([tr], sink=str(tmp_path / "hb2.ndjson"),
                         interval_s=5.0)
    hb2.set_provider(lambda: 1 / 0)
    hb2.start()
    hb2.stop()
    assert len(open(str(tmp_path / "hb2.ndjson")).readlines()) == 2
    # a crashed run's final line carries ok=false (the streamed
    # wrapper's exception path calls stop(ok=False)): done alone must
    # never read as success
    hb3 = tele.Heartbeat([tr], sink=str(tmp_path / "hb3.ndjson"),
                         interval_s=5.0)
    hb3.start()
    hb3.stop(ok=False)
    crash_lines = [json.loads(l) for l in open(str(tmp_path / "hb3.ndjson"))]
    assert crash_lines[0]["ok"] is True
    assert crash_lines[-1] == {**crash_lines[-1], "done": True, "ok": False}


def test_heartbeat_disabled_is_a_noop(tmp_path, monkeypatch):
    """No sink configured => the streamed pipeline constructs no
    heartbeat, flips no global state, and emits nothing."""
    from adam_tpu.pipelines import streamed as st

    monkeypatch.delenv("ADAM_TPU_PROGRESS", raising=False)
    tele.TRACE.recording = False  # fixture restores the entry value
    tr = tele.Tracer(recording=True)
    assert st._start_heartbeat(tr, None) is None
    assert tele.TRACE.recording is False
    st._stop_heartbeat(None)  # no-op on the disabled path
    # with a sink, global recording flips on for the heartbeat's
    # lifetime and is restored on stop — along with the recorded state,
    # so back-to-back runs cannot sum counters into each other's beats
    hb = st._start_heartbeat(tr, str(tmp_path / "hb.ndjson"))
    assert hb is not None and tele.TRACE.recording is True
    tele.TRACE.count(tele.C_PARTS_WRITTEN, 3)  # a mid-run parquet count
    st._stop_heartbeat(hb)
    assert tele.TRACE.recording is False
    assert tele.TRACE.snapshot()["counters"] == {}


# --------------------------------------------------------------------------
# device ledger: transfer accounting / compile ledger / HBM
# --------------------------------------------------------------------------
def test_transfer_ledger_attributes_per_device_and_pass():
    """record_transfer lands the byte counters, the per-direction
    throughput histograms, and a transfers section keyed by device and
    by the thread-local pass_scope; absorb() merges all of it."""
    tr = tele.Tracer(recording=True)
    with tele.pass_scope("a"):
        tr.record_transfer("h2d", 1000, 0.001, device="0")
        with tele.pass_scope("observe"):  # inner scope shadows outer
            tr.record_transfer("d2h", 4000, 0.002, device="0")
    tr.record_transfer("d2h", 500, 0.0, device="1")  # no wall -> no bps
    snap = tr.snapshot()
    assert snap["counters"][tele.C_H2D_BYTES] == 1000
    assert snap["counters"][tele.C_D2H_BYTES] == 4500
    assert snap["transfers"]["h2d"]["0"]["a"]["bytes"] == 1000
    assert snap["transfers"]["d2h"]["0"]["observe"]["count"] == 1
    assert snap["transfers"]["d2h"]["1"][tele.PASS_OTHER]["bytes"] == 500
    # throughput histograms: 1 MB/s and 2 MB/s observed; the zero-wall
    # transfer contributed bytes but no bps sample
    assert snap["histograms"][tele.H_H2D_BPS]["count"] == 1
    assert snap["histograms"][tele.H_D2H_BPS]["count"] == 1
    assert snap["histograms"][tele.H_D2H_BPS]["max"] == pytest.approx(2e6)
    # disabled tracer records nothing
    off = tele.Tracer(recording=False)
    off.record_transfer("h2d", 10, 0.1, device="0")
    assert off.snapshot()["transfers"] == {}
    # absorb merges the per-(device, pass) aggregates additively
    dst = tele.Tracer(recording=True)
    dst.record_transfer("h2d", 24, 0.001, device="0", pass_name="a")
    dst.absorb(tr)
    merged = dst.snapshot()["transfers"]
    assert merged["h2d"]["0"]["a"] == {
        "count": 2, "bytes": 1024, "seconds": pytest.approx(0.002),
    }


def test_compile_ledger_hit_miss_and_in_window_flag():
    """First dispatch of a (kernel, shape, device) triple is a miss
    (flagged in_window outside a prewarm scope), later dispatches are
    hits; a raising dispatch gives its claim back for the retry."""
    from adam_tpu.utils import compile_ledger as cl

    cl.reset()
    tele.TRACE.recording = True
    tele.TRACE.reset()
    key = ("test.kernel", 128, 64)
    with cl.prewarm_scope():
        with cl.track(key, None):
            pass  # "compile" under prewarm
    with cl.track(key, None):
        pass  # warm now -> hit
    with cl.track(("test.kernel", 256, 64), None):
        pass  # new shape at a dispatch site -> in-window miss
    with pytest.raises(RuntimeError):
        with cl.track(("test.kernel", 512, 64), None):
            raise RuntimeError("transient")
    with cl.track(("test.kernel", 512, 64), None):
        pass  # the discarded claim makes the retry a (recorded) miss
    snap = tele.TRACE.snapshot()
    assert snap["counters"][tele.C_COMPILE_MISSES] == 3
    assert snap["counters"][tele.C_COMPILE_HITS] == 1
    assert snap["counters"][tele.C_COMPILE_IN_WINDOW] == 2
    entries = snap["compiles"]["entries"]
    assert [e["in_window"] for e in entries] == [False, True, True]
    assert entries[0]["kernel"] == "test.kernel"
    assert entries[0]["shape"] == [128, 64]
    assert entries[0]["device"] == "default"
    assert snap["histograms"][tele.H_COMPILE_SECONDS]["count"] == 3
    cl.reset()


def test_hbm_ledger_tracks_peak_and_key_stability():
    tr = tele.Tracer(recording=True)
    tr.record_hbm("0", 1000, peak_bytes=1500)
    tr.record_hbm("0", 800)
    tr.record_hbm("1", 2000)
    snap = tr.snapshot()
    assert snap["hbm"]["0"] == {"last": 800, "peak": 1500, "n": 2}
    assert snap["hbm"]["1"] == {"last": 2000, "peak": 2000, "n": 1}
    # absorb keeps the max peak
    dst = tele.Tracer(recording=True)
    dst.record_hbm("0", 3000)
    dst.absorb(tr)
    assert dst.snapshot()["hbm"]["0"] == {"last": 800, "peak": 3000, "n": 3}
    # the CPU bench leg zero-fills the ledger sections key-stably
    ks = tele.key_stable_snapshot(tele.Tracer(recording=True))
    assert ks["transfers"] == {"h2d": {}, "d2h": {}}
    assert ks["compiles"] == {"entries": [], "dropped": 0}
    assert ks["hbm"] == {}
    for name in (tele.C_H2D_BYTES, tele.C_D2H_BYTES,
                 tele.C_COMPILE_HITS, tele.C_COMPILE_MISSES):
        assert ks["counters"][name] == 0
    for name in (tele.H_H2D_BPS, tele.H_D2H_BPS, tele.H_COMPILE_SECONDS):
        assert ks["histograms"][name]["count"] == 0


def test_heartbeat_v2_carries_transfer_and_hbm_fields(tmp_path):
    """The /2 schema fields: transfer byte totals from the counters, HBM
    as {} + null on backends without memory stats (the explicit
    unsupported marker, distinct from zeros)."""
    tr = tele.Tracer(recording=True)
    tr.record_transfer("h2d", 12345, 0.001, device="0", pass_name="a")
    tr.record_transfer("d2h", 54321, 0.002, device="0", pass_name="apply")
    hb = tele.Heartbeat([tr], sink=str(tmp_path / "hb.ndjson"),
                        interval_s=5.0)
    hb.set_devices([])  # no devices -> unsupported marker path
    hb.start()
    hb.stop()
    lines = [json.loads(l) for l in open(str(tmp_path / "hb.ndjson"))]
    assert lines[-1]["schema"] == "adam_tpu.heartbeat/7"
    assert lines[-1]["h2d_bytes"] == 12345
    assert lines[-1]["d2h_bytes"] == 54321
    assert lines[-1]["hbm_bytes_in_use"] == {}
    assert lines[-1]["hbm_peak_bytes"] is None
    for l in lines:
        assert tuple(l.keys()) == tele.HEARTBEAT_FIELDS


def test_heartbeat_rotation_caps_file_size(tmp_path, monkeypatch):
    """Past ADAM_TPU_PROGRESS_MAX_BYTES the sink rotates to <path>.1
    and a fresh file continues — no line is lost or torn across the
    rotation, and seq stays monotonic across both files."""
    monkeypatch.setenv("ADAM_TPU_PROGRESS_MAX_BYTES", "600")
    tr = tele.Tracer(recording=True)
    p = str(tmp_path / "hb.ndjson")
    hb = tele.Heartbeat([tr], sink=p, interval_s=60.0)
    hb.set_devices([])
    hb.start()
    for _ in range(6):  # each line is a few hundred bytes
        hb._emit(done=False)
    hb.stop()
    rotated = p + ".1"
    assert os.path.exists(rotated), "no rotation happened"
    assert os.path.getsize(p) < 1200
    all_lines = []
    for path in (rotated, p):
        for raw in open(path):
            assert raw.endswith("\n")
            all_lines.append(json.loads(raw))
    # rotation keeps only the newest two files (bounded disk is the
    # point): the surviving seqs are contiguous and end at the final
    # line — nothing torn, nothing duplicated
    seqs = [l["seq"] for l in all_lines]
    assert seqs == list(range(seqs[0], seqs[0] + len(seqs)))
    assert all_lines[-1]["done"] is True
    # rotation happens BEFORE each write, so the final done=true line
    # is always in the LIVE file — a tailer (`adam-tpu top`) watching
    # the sink path must never have its exit line rotated away
    live = [json.loads(raw) for raw in open(p)]
    assert live and live[-1]["done"] is True
    monkeypatch.delenv("ADAM_TPU_PROGRESS_MAX_BYTES")
    assert tele.progress_max_bytes() == 64 * 1024 * 1024
    monkeypatch.setenv("ADAM_TPU_PROGRESS_MAX_BYTES", "bogus")
    assert tele.progress_max_bytes() == 64 * 1024 * 1024
    monkeypatch.setenv("ADAM_TPU_PROGRESS_MAX_BYTES", "0")
    assert tele.progress_max_bytes() == 0


# --------------------------------------------------------------------------
# trace context: job-scoped distributed traces (docs/OBSERVABILITY.md)
# --------------------------------------------------------------------------
def test_mint_trace_id_shape_and_uniqueness():
    ids = {tele.mint_trace_id() for _ in range(64)}
    assert len(ids) == 64
    for tid in ids:
        assert re.fullmatch(r"[0-9a-f]{16}", tid), tid


def test_trace_attribution_precedence():
    """Explicit span attr > thread-local trace_scope > tracer default;
    trace_scope(None) is a no-op frame that does NOT shadow an outer
    scope."""
    tr = tele.Tracer(recording=True)
    tr.set_trace("d" * 16)
    with tr.span(tele.SPAN_TOKENIZE, window=0):
        pass
    with tele.trace_scope("5" * 16):
        with tr.span(tele.SPAN_TOKENIZE, window=1):
            pass
        with tele.trace_scope(None):  # no-op frame
            with tr.span(tele.SPAN_TOKENIZE, window=2):
                pass
        with tr.span(tele.SPAN_TOKENIZE, window=3, trace="e" * 16):
            pass
    by_window = {e["args"]["window"]: e.get("trace")
                 for e in tr.events() if e.get("name") != "process_name"}
    assert by_window == {
        0: "d" * 16, 1: "5" * 16, 2: "5" * 16, 3: "e" * 16,
    }
    assert tele.current_trace() is None  # scopes unwound


def test_event_in_trace_matches_stamp_and_fanin_links():
    tid_a, tid_b = "a" * 16, "b" * 16
    tr = tele.Tracer(recording=True)
    with tr.span(tele.SPAN_APPLY_DISPATCH, window=0, trace=tid_a):
        pass
    # the fused cross-job dispatch claims NO single trace; it links
    # every contributing job's {job, window, trace} instead
    with tr.span(tele.SPAN_BATCH_FUSED, kind="markdup", links=[
        {"job": "j1", "window": 0, "trace": tid_a},
        {"job": "j2", "window": 3, "trace": tid_b},
    ]):
        pass
    ev_a = tr.events_for_trace(tid_a)
    ev_b = tr.events_for_trace(tid_b)
    names_a = {e["name"] for e in ev_a}
    assert tele.SPAN_APPLY_DISPATCH in names_a
    assert tele.SPAN_BATCH_FUSED in names_a
    # job B sees the SHARED fused span but not job A's private span
    assert {e["name"] for e in ev_b if e["name"] != "process_name"} \
        == {tele.SPAN_BATCH_FUSED}
    assert not tr.events_for_trace("c" * 16)


def test_per_trace_aggregates_survive_ring_eviction():
    tid = "f" * 16
    tr = tele.Tracer(recording=True, capacity=8)
    for i in range(64):
        with tr.span(tele.SPAN_TOKENIZE, window=i, trace=tid):
            pass
    assert len(tr.events_for_trace(tid)) <= 8  # ring evicted most
    agg = tr.snapshot()["traces"][tid]
    assert agg["events"] == 64  # ...but the ledger kept counting
    assert agg["total_s"] >= 0.0


def test_chrome_trace_export_filters_to_one_trace():
    tid_a, tid_b = "a" * 16, "b" * 16
    tr = tele.Tracer(recording=True)
    with tr.span(tele.SPAN_APPLY_DISPATCH, window=0, trace=tid_a):
        pass
    with tr.span(tele.SPAN_APPLY_FETCH, window=9, trace=tid_b):
        pass
    with tr.span(tele.SPAN_BATCH_FUSED, links=[
        {"job": "j1", "window": 0, "trace": tid_a},
    ]):
        pass
    doc = tr.to_chrome_trace(tid_a)
    names = {e["name"] for e in doc["traceEvents"]
             if e.get("ph") == "X"}
    assert tele.SPAN_APPLY_DISPATCH in names
    assert tele.SPAN_BATCH_FUSED in names
    assert tele.SPAN_APPLY_FETCH not in names  # job B's private span
    # the per-trace ledger is filtered with the export
    assert set(doc["traces"]) == {tid_a}
    # the unfiltered export carries both jobs' aggregates
    assert set(tr.to_chrome_trace()["traces"]) == {tid_a, tid_b}


def test_absorb_carries_per_trace_aggregates():
    """A job-scoped run tracer folds into the global tracer without
    losing its trace ledger (the /trace surface reads the global)."""
    tid = "1" * 16
    run = tele.Tracer(recording=True)
    run.set_trace(tid)
    with run.span(tele.SPAN_TOKENIZE, window=0):
        pass
    glob = tele.Tracer(recording=True)
    glob.absorb(run)
    assert glob.snapshot()["traces"][tid]["events"] == 1
    assert len(glob.events_for_trace(tid)) == 1


def test_merge_snapshots_traces_associative():
    """Per-trace aggregates are plain sums: any grouping of host
    snapshots yields the same traces section (the satellite's
    associativity requirement)."""
    def host(tid_events):
        tr = tele.Tracer(recording=True)
        for tid, n in tid_events:
            for i in range(n):
                with tr.span(tele.SPAN_TOKENIZE, window=i, trace=tid):
                    pass
        return tr.snapshot()

    a = host([("a" * 16, 2)])
    b = host([("a" * 16, 3), ("b" * 16, 1)])
    c = host([("b" * 16, 5)])
    flat = tele.merge_snapshots([a, b, c])["traces"]
    left = tele.merge_snapshots(
        [tele.merge_snapshots([a, b]), c])["traces"]
    right = tele.merge_snapshots(
        [a, tele.merge_snapshots([b, c])])["traces"]
    for merged in (left, right):
        assert set(merged) == set(flat)
        for tid in flat:
            assert merged[tid]["events"] == flat[tid]["events"]
            assert merged[tid]["total_s"] == \
                pytest.approx(flat[tid]["total_s"])
    assert flat["a" * 16]["events"] == 5
    assert flat["b" * 16]["events"] == 6


def test_active_trace_registry_is_refcounted():
    tid = "9" * 16
    assert tid not in tele.active_traces()
    tele.activate_trace(tid)
    tele.activate_trace(tid)  # re-entrant (recovery re-runs)
    assert tid in tele.active_traces()
    tele.deactivate_trace(tid)
    assert tid in tele.active_traces()
    tele.deactivate_trace(tid)
    assert tid not in tele.active_traces()
    tele.activate_trace(None)  # no-op, never raises
    tele.deactivate_trace(None)


def test_prometheus_exposition_from_snapshot():
    """gateway/metrics.render_prometheus: valid exposition text off a
    live snapshot — counters, gauges, cumulative histogram buckets,
    and the trace gauges; every series name valid per the grammar."""
    from adam_tpu.gateway import metrics as gw_metrics

    tr = tele.Tracer(recording=True)
    tr.count(tele.C_READS_INGESTED, 7)
    tr.gauge(tele.G_POOL_DEPTH, 3)
    for v in (0.001, 0.01, 0.1):
        tr.observe(tele.H_FETCH_SECONDS, v)
    text = gw_metrics.render_prometheus(tr.snapshot())
    assert text.endswith("\n")
    assert "adam_tpu_reads_ingested 7" in text
    assert "adam_tpu_parquet_pool_queue_depth 3" in text
    assert "adam_tpu_device_fetch_seconds_count 3" in text
    assert 'le="+Inf"' in text
    assert "adam_tpu_traces_active" in text
    # grammar: every sample line's metric name is valid; buckets are
    # cumulative (non-decreasing per series)
    bucket_acc = {}
    for line in text.splitlines():
        if line.startswith("#") or not line:
            continue
        name = line.split("{")[0].split(" ")[0]
        assert tele.prometheus_name_valid(name), line
        if "_bucket" in line:
            v = float(line.rsplit(" ", 1)[1])
            assert v >= bucket_acc.get(name, 0.0), line
            bucket_acc[name] = v
    # HELP/TYPE precede every series
    assert text.index("# TYPE adam_tpu_reads_ingested counter") \
        < text.index("adam_tpu_reads_ingested 7")


def test_prometheus_exposition_sanitizes_display_timer_names():
    """The 8 legacy display-style timer names ('BGZF Codec (native)')
    can reach a snapshot via span-duration auto-histograms; the
    renderer sanitizes them rather than emitting invalid series."""
    from adam_tpu.gateway import metrics as gw_metrics

    snap = {"counters": {}, "gauges": {},
            "histograms": {"BGZF Codec (native)": {
                "count": 1, "sum": 0.5, "min": 0.5, "max": 0.5,
                "buckets": {"0": 1},
            }}}
    text = gw_metrics.render_prometheus(snap)
    for line in text.splitlines():
        if line.startswith("#") or not line:
            continue
        name = line.split("{")[0].split(" ")[0]
        assert tele.prometheus_name_valid(name), line


def test_heartbeat_v6_trace_and_incident_fields(tmp_path):
    """/6 appends active_traces / metrics_scrapes / last_incident /
    last_incident_age_s — populated from the live registries."""
    from adam_tpu.utils import incidents

    tid = "c" * 16
    tr = tele.Tracer(recording=True)
    tr.count(tele.C_GW_SCRAPES, 4)
    incidents.install(str(tmp_path))
    tele.activate_trace(tid)
    try:
        incidents.maybe_record("hedge.fired", tracer=tr,
                               reason="test bundle")
        p = str(tmp_path / "hb.ndjson")
        hb = tele.Heartbeat([tr], sink=p, interval_s=60.0)
        hb.set_devices([])
        hb.start()
        hb.stop()
        lines = [json.loads(raw) for raw in open(p)]
    finally:
        tele.deactivate_trace(tid)
        incidents.uninstall()
    line = lines[-1]
    assert line["schema"] == "adam_tpu.heartbeat/7"
    assert list(line) == list(tele.HEARTBEAT_FIELDS)
    assert line["active_traces"] >= 1
    assert line["metrics_scrapes"] == 4
    assert line["last_incident"].startswith("inc-")
    assert line["last_incident_age_s"] >= 0.0


def test_merge_snapshots_health_missing_side_key_stable():
    """A host snapshot without a health section (host-only worker, or
    an older artifact) merges key-stably: the present side passes
    through and the merged doc still carries the key."""
    row = {"state": "suspect", "score": 0.4,
           "reason": "slow fetch", "transitions": 2}
    with_health = {"spans": {}, "health": {"0": dict(row)}}
    without = {"spans": {}}  # no health key at all
    merged = tele.merge_snapshots([with_health, without])
    assert merged["health"] == {"0": row}
    # both orders, and an all-missing merge still carries the key
    assert tele.merge_snapshots([without, with_health])["health"] == \
        {"0": row}
    assert tele.merge_snapshots([without, without])["health"] == {}


def test_merge_snapshots_health_worst_state_wins():
    a = {"health": {"0": {"state": "healthy", "score": 0.9,
                          "reason": "", "transitions": 1}}}
    b = {"health": {"0": {"state": "evicted", "score": 0.1,
                          "reason": "SDC mismatch", "transitions": 3}}}
    for order in ([a, b], [b, a]):
        got = tele.merge_snapshots(order)["health"]["0"]
        assert got["state"] == "evicted"
        assert got["reason"] == "SDC mismatch"
        assert got["score"] == pytest.approx(0.1)
        assert got["transitions"] == 4


def test_merge_snapshots_quota_missing_side_key_stable():
    row = {"charges": 3, "bytes": 100, "compute_s": 1.5,
           "budget_bytes": 1000, "budget_compute_s": None}
    with_quota = {"quota": {"t1": dict(row)}}
    without = {"spans": {}}
    for order in ([with_quota, without], [without, with_quota]):
        assert tele.merge_snapshots(order)["quota"] == {"t1": row}
    assert tele.merge_snapshots([without])["quota"] == {}


def test_merge_snapshots_quota_sums_spend_keeps_budgets():
    a = {"quota": {"t1": {"charges": 2, "bytes": 10, "compute_s": 1.0,
                          "budget_bytes": None,
                          "budget_compute_s": None}}}
    b = {"quota": {"t1": {"charges": 1, "bytes": 5, "compute_s": 0.5,
                          "budget_bytes": 1 << 20,
                          "budget_compute_s": 60.0},
                   "t2": {"charges": 9, "bytes": 0, "compute_s": 0.0,
                          "budget_bytes": None,
                          "budget_compute_s": None}}}
    got = tele.merge_snapshots([a, b])["quota"]
    assert got["t1"]["charges"] == 3
    assert got["t1"]["bytes"] == 15
    assert got["t1"]["compute_s"] == pytest.approx(1.5)
    # budgets are configuration: first non-None wins, never summed
    assert got["t1"]["budget_bytes"] == 1 << 20
    assert got["t1"]["budget_compute_s"] == 60.0
    assert got["t2"]["charges"] == 9


# --------------------------------------------------------------------------
# Profiler annotations: recorded spans on the profiler's host plane
# --------------------------------------------------------------------------
class _FakeAnnotation:
    """Stands in for ``jax.profiler.TraceAnnotation``; logs enter/exit."""

    enabled = True
    log: list = []

    def __init__(self, name):
        self.name = name

    @classmethod
    def is_enabled(cls):
        return cls.enabled

    def __enter__(self):
        self.log.append(("enter", self.name))

    def __exit__(self, *exc):
        self.log.append(("exit", self.name))


@pytest.fixture
def fake_annotation(monkeypatch):
    _FakeAnnotation.log = []
    monkeypatch.setattr(tele, "_ANNOTATION", _FakeAnnotation)
    return _FakeAnnotation


@pytest.mark.parametrize("recording,enabled,expect", [
    (True, True, True),
    (True, False, False),
    (False, True, False),
    (False, False, False),
])
def test_span_opens_annotation_only_when_recording_and_collecting(
    fake_annotation, monkeypatch, recording, enabled, expect
):
    monkeypatch.setattr(fake_annotation, "enabled", enabled)
    tr = tele.Tracer(recording=recording)
    with tr.span(tele.SPAN_BQSR_OBSERVE_PREP, reads=3):
        pass
    want = [("enter", "bqsr.observe.prep"), ("exit", "bqsr.observe.prep")]
    assert fake_annotation.log == (want if expect else [])
    # the recorded span is the same either way
    assert tr.snapshot()["spans"].get("bqsr.observe.prep", {}).get(
        "count", 0) == (1 if recording else 0)


def test_annotations_nest_like_the_spans(fake_annotation):
    tele.TRACE.recording = False
    tr = tele.Tracer(recording=True)
    with tr.span(tele.SPAN_TAIL):
        with tr.span(tele.SPAN_REALIGN_TARGETS):
            pass
        with tele.TRACE.span(tele.SPAN_OBSERVE):  # off: no annotation
            pass
        with tr.span(tele.SPAN_OBSERVE):
            with tr.span(tele.SPAN_BQSR_OBSERVE_PREP):
                pass
    assert fake_annotation.log == [
        ("enter", "streamed.tail"),
        ("enter", "realign.targets"), ("exit", "realign.targets"),
        ("enter", "streamed.observe"),
        ("enter", "bqsr.observe.prep"), ("exit", "bqsr.observe.prep"),
        ("exit", "streamed.observe"),
        ("exit", "streamed.tail"),
    ]
    ev = {e["name"]: e for e in tr.events()}
    assert ev["bqsr.observe.prep"]["parent"] == "streamed.observe"


def test_annotation_closes_when_the_span_raises(fake_annotation):
    tr = tele.Tracer(recording=True)
    with pytest.raises(KeyError):
        with tr.span(tele.SPAN_REALIGN_DECIDE):
            raise KeyError("x")
    assert fake_annotation.log[-1] == ("exit", "realign.decide")
    assert tr.snapshot()["spans"]["realign.decide"]["count"] == 1


def test_annotation_class_is_looked_up_only_once_jax_is_loaded(monkeypatch):
    monkeypatch.setattr(tele, "_ANNOTATION", None)
    monkeypatch.delitem(sys.modules, "jax")
    assert tele._annotation_class() is None  # nothing imported
    assert "jax" not in sys.modules
    monkeypatch.undo()
    monkeypatch.setattr(tele, "_ANNOTATION", None)
    import jax.profiler

    assert tele._annotation_class() is jax.profiler.TraceAnnotation


def test_span_lands_on_the_profiler_host_plane(tmp_path):
    """With a real profiler session, a recorded span is an event of the
    same name on the host plane, inside the enclosing annotation."""
    import jax.profiler as jp
    from jax.profiler import ProfileData

    tr = tele.Tracer(recording=True)
    jp.start_trace(str(tmp_path))
    with jp.TraceAnnotation("outer"):
        with tr.span(tele.SPAN_REALIGN_CONSENSUS):
            pass
    jp.stop_trace()
    with tr.span(tele.SPAN_REALIGN_CONSENSUS):  # no session: no event
        pass
    path = sorted(tmp_path.rglob("*.xplane.pb"))[-1]
    evs = {}
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    evs.setdefault(e.name, []).append(
                        (e.start_ns, e.start_ns + e.duration_ns))
    assert len(evs["realign.consensus"]) == 1
    (s, e), = evs["realign.consensus"]
    (os_, oe), = evs["outer"]
    assert os_ <= s <= e <= oe
    assert tr.snapshot()["spans"]["realign.consensus"]["count"] == 2
