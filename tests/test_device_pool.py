"""Multi-chip device pool: round-robin parity, prewarm, plumbing.

The 8 virtual CPU devices (tests/conftest.py backend trick) stand in
for an 8-chip topology: the streamed flagship with ``devices=4`` must
produce **bit-identical** output to ``devices=1`` — Parquet part
contents, recalibration table, flagstat — because every merge is a
host-side sum over per-window parts in window order (the pool changes
WHERE work runs, never what it computes).  Prewarm must compile every
grid-quantized kernel shape exactly once per device, concurrently, and
never twice per process.
"""

import hashlib
import os
import sys

import numpy as np
import pytest

from adam_tpu.parallel import device_pool as dp
from adam_tpu.utils import telemetry as tele

sys.path.insert(
    0, os.path.join(os.path.dirname(__file__), "..", "tools")
)


# ---------------------------------------------------------------------------
# Device-count resolution
# ---------------------------------------------------------------------------
def test_resolve_device_count_env_and_cap(monkeypatch):
    import jax

    attached = len(jax.devices())
    assert attached == 8  # the conftest virtual mesh this suite assumes
    monkeypatch.delenv("ADAM_TPU_DEVICES", raising=False)
    assert dp.resolve_device_count() == attached
    monkeypatch.setenv("ADAM_TPU_DEVICES", "3")
    assert dp.resolve_device_count() == 3
    # explicit arg beats env; beyond-topology requests cap, not raise
    assert dp.resolve_device_count(2) == 2
    assert dp.resolve_device_count(attached + 5) == attached
    # malformed env values degrade (warn + all attached); only the
    # explicit CLI arg is a hard error
    for bad in ("not-an-int", "0", "-3"):
        monkeypatch.setenv("ADAM_TPU_DEVICES", bad)
        assert dp.resolve_device_count() == attached
    with pytest.raises(ValueError, match="devices"):
        dp.resolve_device_count(0)


def test_make_pool_single_device_falls_back():
    assert dp.make_pool(1) is None
    pool = dp.make_pool(4)
    assert pool is not None and pool.n == 4
    # round-robin: window i -> device i % n
    assert [pool.device_index(i) for i in range(6)] == [0, 1, 2, 3, 0, 1]
    assert pool.device(5) is pool.devices[1]


def test_pool_put_commits_to_round_robin_device():
    import jax

    pool = dp.DevicePool(limit=3)
    for i in range(4):
        arr = pool.put(np.arange(8), i)
        (dev,) = arr.devices()
        assert dev == pool.device(i)
    jax.block_until_ready(arr)


# ---------------------------------------------------------------------------
# Prewarm: exactly once per (kernel shape, device), process-wide
# ---------------------------------------------------------------------------
def test_prewarm_compiles_each_shape_once_per_device():
    dp.reset_prewarm_cache()
    try:
        pool = dp.DevicePool(limit=4)
        calls: list = []

        def make(key):
            def fn(dev):
                calls.append((key, dev.id))
            return (key, fn)

        entries = [make(("k1", 1024, 128)), make(("k2", 1024, 128))]
        tr = tele.Tracer(recording=True)
        n = pool.prewarm(entries, tracer=tr)
        assert n == 2 * pool.n
        # every (entry, device) pair exactly once
        assert sorted(calls) == sorted(
            (key, d.id) for key, _fn in entries for d in pool.devices
        )
        # per-compile spans carry device attribution into the tracer
        snap = tr.snapshot()
        assert snap["spans"][tele.SPAN_POOL_PREWARM_COMPILE]["count"] == n
        assert set(
            snap["device_spans"][tele.SPAN_POOL_PREWARM_COMPILE]
        ) == {str(k) for k in range(pool.n)}
        assert snap["counters"][tele.C_POOL_PREWARM_COMPILES] == n

        # second prewarm in the same process: nothing to do (the bench's
        # warmup-run-then-timed-run pattern relies on this)
        calls.clear()
        assert pool.prewarm(entries, tracer=tr) == 0
        assert calls == []
        # a second pool over the same devices is also already warm
        assert dp.DevicePool(limit=4).prewarm(entries, tracer=tr) == 0
        # ... but a device the first pool didn't cover is not
        assert dp.DevicePool(limit=5).prewarm(entries, tracer=tr) == 2
    finally:
        dp.reset_prewarm_cache()


def test_prewarm_failure_degrades_and_stays_retryable():
    """A failed compile must not abort the run (prewarm is an
    optimization) and must discard its claim so a later prewarm
    retries it."""
    dp.reset_prewarm_cache()
    try:
        pool = dp.DevicePool(limit=2)
        attempts: list = []
        fail_next = [True]

        def fn(dev):
            attempts.append(dev.id)
            if fail_next[0]:
                raise RuntimeError("transient compile RPC failure")

        entries = [(("flaky", 1, 1), fn)]
        assert pool.prewarm(entries) == 0  # both compiles failed, no raise
        assert sorted(attempts) == [0, 1]
        fail_next[0] = False
        assert pool.prewarm(entries) == 2  # claims were discarded: retried
        assert pool.prewarm(entries) == 0  # now warm
    finally:
        dp.reset_prewarm_cache()


def test_streamed_prewarm_entries_cover_enabled_kernels():
    from adam_tpu.formats.batch import pack_reads

    recs = [
        dict(name=f"r{i}", flags=0, contig_idx=0, start=100 + i, mapq=60,
             cigar="10M", seq="ACGTACGTAC", qual="I" * 10, read_group_idx=0)
        for i in range(4)
    ]
    batch, _side = pack_reads(recs)
    b = batch.to_numpy()
    keys = [k[0] for k, _fn in dp.streamed_prewarm_entries(b, 2)]
    assert keys == [
        "markdup.columns", "bqsr.observe_packed", "bqsr.apply_pack2",
    ]
    keys = [
        k[0] for k, _fn in dp.streamed_prewarm_entries(
            b, 2, mark_duplicates=False
        )
    ]
    assert keys == ["bqsr.observe_packed", "bqsr.apply_pack2"]
    keys = [
        k[0] for k, _fn in dp.streamed_prewarm_entries(
            b, 2, fused_n_cyc=41
        )
    ]
    assert keys[-1] == "bqsr.fused_bc"
    assert dp.streamed_prewarm_entries(
        b, 2, mark_duplicates=False, recalibrate=False
    ) == []


def test_streamed_prewarm_entries_execute():
    """The dummy-arg warm calls really compile+run the kernel set (shape
    or dtype drift between prewarm and the real dispatches would show up
    here as a trace error)."""
    from adam_tpu.formats.batch import pack_reads

    recs = [
        dict(name=f"r{i}", flags=0, contig_idx=0, start=100 + i, mapq=60,
             cigar="10M", seq="ACGTACGTAC", qual="I" * 10, read_group_idx=0)
        for i in range(4)
    ]
    batch, _side = pack_reads(recs)
    dp.reset_prewarm_cache()
    try:
        pool = dp.DevicePool(limit=2)
        entries = dp.streamed_prewarm_entries(batch.to_numpy(), 2)
        assert pool.prewarm(entries) == len(entries) * 2
    finally:
        dp.reset_prewarm_cache()


# ---------------------------------------------------------------------------
# Streamed multi-device parity: bit-identical to the single-device run
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def parity_runs(tmp_path_factory):
    """One streamed run per device count over the same WGS-shaped input,
    pinned to the device backend on the virtual mesh."""
    from make_wgs_sam import make_wgs

    from adam_tpu.pipelines.streamed import transform_streamed

    d = tmp_path_factory.mktemp("device_pool")
    path = str(d / "in.sam")
    make_wgs(path, 2048, 100, n_contigs=2, contig_len=30_000,
             indel_every=800, snp_every=400)
    os.environ["ADAM_TPU_BQSR_BACKEND"] = "device"
    runs = {}
    try:
        for n in (1, 4):
            out = str(d / f"out{n}.adam")
            csv = str(d / f"obs{n}.csv")
            stats = transform_streamed(
                path, out, window_reads=512, devices=n,
                dump_observations=csv,
            )
            runs[n] = (out, csv, stats)
    finally:
        os.environ.pop("ADAM_TPU_BQSR_BACKEND", None)
    return runs


def test_streamed_device_pool_parts_bit_identical(parity_runs):
    """Every Parquet part file is byte-identical between devices=1 and
    devices=4 — same windows, same flags/quals/sidecars, same encode."""
    out1, _, stats1 = parity_runs[1]
    out4, _, stats4 = parity_runs[4]
    assert stats1["n_devices"] == 1 and stats4["n_devices"] == 4
    parts1 = sorted(f for f in os.listdir(out1) if f.startswith("part-"))
    parts4 = sorted(f for f in os.listdir(out4) if f.startswith("part-"))
    assert parts1 == parts4 and parts1
    for f in parts1:
        h1 = hashlib.sha256(
            open(os.path.join(out1, f), "rb").read()
        ).hexdigest()
        h4 = hashlib.sha256(
            open(os.path.join(out4, f), "rb").read()
        ).hexdigest()
        assert h1 == h4, f


def test_streamed_device_pool_recal_table_identical(parity_runs):
    """The merged observation table (the recalibration table's source of
    truth) is identical: per-device histograms merged host-side in
    window order cannot drift from the single-device sum."""
    _, csv1, _ = parity_runs[1]
    _, csv4, _ = parity_runs[4]
    t1 = open(csv1).read()
    assert t1 == open(csv4).read()
    assert len(t1.splitlines()) > 1  # a real table, not an empty header


def test_streamed_device_pool_flagstat_identical(parity_runs):
    from adam_tpu.io import context
    from adam_tpu.ops.flagstat import format_flagstat

    out1, _, _ = parity_runs[1]
    out4, _, _ = parity_runs[4]
    fs1 = format_flagstat(*context.load_alignments(out1).flagstat())
    fs4 = format_flagstat(*context.load_alignments(out4).flagstat())
    assert fs1 == fs4
    assert "in total" in fs1


def test_streamed_device_pool_telemetry(parity_runs):
    """The pool run reports its fan-out: n_devices in the stats dict
    and the prewarm umbrella wall in the derived view (disjoint from
    pass A's row — the stage walls must still sum to the pipeline
    wall, not double-count the compile time)."""
    _, _, stats = parity_runs[4]
    assert stats["n_devices"] == 4
    assert stats["prewarm_s"] > 0
    assert stats["ingest_pass_s"] >= 0
    # the umbrella is wall time: it fits inside the total, which the
    # sum of concurrent per-compile spans generally would not
    assert stats["prewarm_s"] <= stats["total_s"]


def test_chrome_trace_mirrors_device_tracks():
    """Device-attributed spans land on one ``device:<k>`` track per chip
    next to their host-thread track."""
    tr = tele.Tracer(recording=True)
    with tr.span(tele.SPAN_APPLY_DISPATCH, window=0, device=2):
        pass
    with tr.span(tele.SPAN_APPLY_DISPATCH, window=1, device=5):
        pass
    with tr.span(tele.SPAN_SOLVE):
        pass
    doc = tr.to_chrome_trace()
    names = {
        e["args"]["name"] for e in doc["traceEvents"]
        if e.get("ph") == "M"
    }
    assert {"device:2", "device:5"} <= names
    dev_events = [
        e for e in doc["traceEvents"]
        if e.get("ph") == "X" and (e.get("args") or {}).get("device") == 2
    ]
    # once on the host thread track, mirrored once on the device track
    assert len(dev_events) == 2
    assert len({e["tid"] for e in dev_events}) == 2
    # per-device aggregates ride the snapshot for occupancy/skew reports
    snap = tr.snapshot()
    per = snap["device_spans"][tele.SPAN_APPLY_DISPATCH]
    assert set(per) == {"2", "5"}
    assert per["2"]["count"] == 1


# ---------------------------------------------------------------------------
# Eviction attribution: replayed work vs organic occupancy
# ---------------------------------------------------------------------------
def test_span_attrs_mark_replay_scope():
    """Inside a replay_scope every device-attributed span picks up
    ``replay=1`` — in any layer, with no API plumbing — so the
    ``device_spans`` aggregation can keep a survivor's replay burden
    apart from its organic work."""
    import jax

    dev = jax.devices()[0]
    base = dp.span_attrs(dev)
    assert "replay" not in base
    with dp.replay_scope():
        marked = dp.span_attrs(dev)
        assert marked["device"] == base["device"]
        assert marked["replay"] == 1
        with dp.replay_scope():  # reentrant
            assert dp.span_attrs(dev)["replay"] == 1
        assert dp.in_replay()
    assert not dp.in_replay()
    # the single-device path stays attribution-free even mid-replay
    with dp.replay_scope():
        assert dp.span_attrs(None) == {}


def test_device_spans_after_evict_keep_original_and_split_replay():
    """After DevicePool.evict, the dead chip's pre-eviction spans stay
    under its original key and the survivor's replayed windows land
    under ``<survivor>:replay`` — the snapshot can no longer conflate
    replayed work with the survivor's own."""
    pool = dp.make_pool(2)
    tr = tele.Tracer(recording=True)
    d0, d1 = pool.devices
    k0, k1 = dp._attr_id(d0), dp._attr_id(d1)
    # organic work on both chips
    with tr.span(tele.SPAN_APPLY_DISPATCH, window=0, **dp.span_attrs(d0)):
        pass
    with tr.span(tele.SPAN_APPLY_DISPATCH, window=1, **dp.span_attrs(d1)):
        pass
    # chip 1 dies; window 1 replays on chip 0 the way streamed.py does:
    # umbrella span attributed to the FAILED chip, nested dispatch
    # inside a replay_scope on the survivor
    assert pool.evict(d1, reason="test", tracer=tr)
    with tr.span(tele.SPAN_POOL_REPLAY, window=1, **dp.span_attrs(d1)), \
            dp.replay_scope():
        with tr.span(tele.SPAN_APPLY_DISPATCH, window=1,
                     **dp.span_attrs(d0)):
            pass
    snap = tr.snapshot()
    disp = snap["device_spans"][tele.SPAN_APPLY_DISPATCH]
    assert disp[str(k0)]["count"] == 1       # organic only
    assert disp[str(k1)]["count"] == 1       # pre-eviction, original key
    assert disp[f"{k0}:replay"]["count"] == 1  # the replayed window
    # the umbrella names the failed chip, eviction counted
    assert snap["device_spans"][tele.SPAN_POOL_REPLAY][str(k1)]["count"] == 1
    assert snap["counters"][tele.C_DEVICE_EVICTED] == 1
    assert pool.alive_devices() == [d0]
