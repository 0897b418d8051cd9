"""Device-resident windows (docs/PERF.md "Device-resident windows"):
ingest-once H2D, cross-pass buffer donation, and the bases half of the
packed tail.

The matrix this file owes:

* kernel-level bit parity — packed-mask observe vs the plain observe,
  the fused bases+quals pack2 vs the plain apply + host packs, and the
  donating jit variants vs their copying twins;
* `ResidentWindow` refcount semantics (retain/release/drop/consumed);
* end-to-end byte parity of the streamed flagship across pool, mesh
  and host backends, with handles pinned at ingest or (the ingest pin
  made to fail) transient per pass;
* the ledger contract — one `ingest` h2d entry per window with
  observe/apply h2d ≈ 0, handles all released (live-bytes gauge back
  to 0: no HBM growth across windows);
* the fault matrix — an eviction in pass B or pass C, a donating pack2
  attempt that consumes its handle and fails, and a mesh degraded
  mid-run each replay byte-identically on transient handles placed from
  the host-retained ingest copy, and a SIGKILL'd resident run resumes
  byte-identically (`--resume`);
* the compile ledger of a streamed run names only the one program per
  pass (markdup columns, packed observe, pack2 apply, fused B→C).
"""

import hashlib
import os
import signal
import subprocess
import sys
import warnings

import numpy as np
import pytest

from adam_tpu.parallel import device_pool as dp
from adam_tpu.parallel import partitioner as part_mod
from adam_tpu.utils import telemetry as tele

sys.path.insert(
    0, os.path.join(os.path.dirname(__file__), "..", "tools")
)


def _sha_parts(d):
    return {
        f: hashlib.sha256(
            open(os.path.join(d, f), "rb").read()
        ).hexdigest()
        for f in os.listdir(d) if f.startswith("part-")
    }


# ---------------------------------------------------------------------------
# Kernel parity: packed masks, pack2, donation-vs-copy
# ---------------------------------------------------------------------------
def _kernel_inputs(seed=1, g=64, gl=64, n_rg=3):
    rng = np.random.default_rng(seed)
    return dict(
        g=g, gl=gl, n_rg=n_rg,
        bases=rng.integers(0, 6, (g, gl)).astype(np.uint8),
        quals=rng.integers(0, 60, (g, gl)).astype(np.uint8),
        lengths=rng.integers(1, gl, g).astype(np.int32),
        flags=rng.integers(0, 4, g).astype(np.int32),
        rg=rng.integers(-1, n_rg - 1, g).astype(np.int32),
        res_ok=rng.random((g, gl)) < 0.6,
        is_mm=rng.random((g, gl)) < 0.2,
        read_ok=rng.random(g) < 0.8,
        has_qual=rng.random(g) < 0.9,
        valid=rng.random(g) < 0.95,
    )


def test_pack_mask_bits_roundtrip():
    from adam_tpu.ops.colpack import pack_mask_bits, unpack_mask_body

    rng = np.random.default_rng(3)
    for g, gl in ((1, 8), (7, 32), (64, 96)):
        m = rng.random((g, gl)) < 0.5
        pk = pack_mask_bits(m)
        assert pk.shape == (g, -(-gl // 8))
        np.testing.assert_array_equal(
            np.asarray(unpack_mask_body(pk, gl)), m
        )


def test_observe_packed_kernel_bit_parity():
    from adam_tpu.ops.colpack import pack_mask_bits
    from adam_tpu.pipelines.bqsr import jit_variant, observe_kernel

    k = _kernel_inputs()
    ref_t, ref_m = observe_kernel(
        k["bases"], k["quals"], k["lengths"], k["flags"], k["rg"],
        k["res_ok"], k["is_mm"], k["read_ok"], k["n_rg"], k["gl"],
    )
    got_t, got_m = jit_variant("observe_packed")(
        k["bases"], k["quals"], k["lengths"], k["flags"], k["rg"],
        pack_mask_bits(k["res_ok"]), pack_mask_bits(k["is_mm"]),
        k["read_ok"], k["n_rg"], k["gl"],
    )
    np.testing.assert_array_equal(np.asarray(ref_t), np.asarray(got_t))
    np.testing.assert_array_equal(np.asarray(ref_m), np.asarray(got_m))
    assert int(np.asarray(ref_t).sum()) > 0  # a real workload


def test_apply_pack2_kernel_bit_parity():
    """The fused bases+quals pack2 emits exactly the host packs of the
    plain apply's output quals (SANGER) and the decoded bases."""
    from adam_tpu.formats import schema
    from adam_tpu.ops.colpack import pack_rows_np
    from adam_tpu.pipelines.bqsr import (
        N_DINUC, N_QUAL, apply_pack2_kernel, apply_table_kernel,
    )

    k = _kernel_inputs(seed=5)
    rng = np.random.default_rng(6)
    tbl = rng.integers(
        0, 50, (k["n_rg"], N_QUAL, 2 * k["gl"] + 1, N_DINUC)
    ).astype(np.uint8)
    args = (k["bases"], k["quals"], k["lengths"], k["flags"], k["rg"],
            k["has_qual"], k["valid"], tbl)
    new_q = np.asarray(apply_table_kernel(*args, k["gl"]))
    pq, pb = apply_pack2_kernel(*args, k["gl"], k["g"] * k["gl"])
    q_lens = np.where(k["valid"] & k["has_qual"], k["lengths"], 0)
    b_lens = np.where(k["valid"], k["lengths"], 0)
    exp_q = pack_rows_np(
        (np.minimum(new_q, 93) + schema.SANGER_OFFSET).astype(np.uint8),
        q_lens,
    )
    exp_b = pack_rows_np(schema.BASE_DECODE_LUT256[k["bases"]], b_lens)
    np.testing.assert_array_equal(np.asarray(pq)[: len(exp_q)], exp_q)
    np.testing.assert_array_equal(np.asarray(pb)[: len(exp_b)], exp_b)
    assert len(exp_q) and len(exp_b)


def test_donating_variants_bit_parity():
    """Donation-vs-copy: the donating jit twins return bitwise the
    plain variants' outputs (on CPU the donation is ignored with a
    warning — the parity contract is what must hold everywhere)."""
    from adam_tpu.ops.colpack import pack_mask_bits
    from adam_tpu.pipelines.bqsr import N_DINUC, N_QUAL, jit_variant

    import jax.numpy as jnp

    k = _kernel_inputs(seed=9)
    rng = np.random.default_rng(10)
    tbl = rng.integers(
        0, 50, (k["n_rg"], N_QUAL, 2 * k["gl"] + 1, N_DINUC)
    ).astype(np.uint8)
    apply_args = (k["bases"], k["quals"], k["lengths"], k["flags"],
                  k["rg"], k["has_qual"], k["valid"], tbl)
    obs_args = (k["bases"], k["quals"], k["lengths"], k["flags"],
                k["rg"], pack_mask_bits(k["res_ok"]),
                pack_mask_bits(k["is_mm"]), k["read_ok"])
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        for kind, args, extra in (
            ("apply_pack2", apply_args, (k["gl"], k["g"] * k["gl"])),
            ("observe_packed", obs_args, (k["n_rg"], k["gl"])),
        ):
            plain = jit_variant(kind, False)(*args, *extra)
            # donated args must be fresh device arrays (donating a
            # committed numpy input is the real call shape)
            placed = tuple(jnp.asarray(a) for a in args)
            donated = jit_variant(kind, True)(*placed, *extra)
            for p, d in (
                zip(plain, donated) if isinstance(plain, tuple)
                else [(plain, donated)]
            ):
                np.testing.assert_array_equal(
                    np.asarray(p), np.asarray(d)
                )


# ---------------------------------------------------------------------------
# ResidentWindow refcount semantics
# ---------------------------------------------------------------------------
def test_resident_window_refcount():
    rw = dp.ResidentWindow(
        0, None, {"bases": np.zeros(4), "quals": np.zeros(4),
                  "lengths": np.zeros(4), "flags": np.zeros(4),
                  "read_group_idx": np.zeros(4)},
        g=4, gl=1, nbytes=160,
    )
    assert rw.alive
    assert len(rw.args()) == 5
    rw.retain()
    assert rw.release() is False  # one ref still held
    assert rw.alive
    assert rw.release() is True   # last ref frees
    assert not rw.alive
    with pytest.raises(RuntimeError):
        rw.get("bases")
    assert rw.release() is False  # idempotent after free

    rw2 = dp.ResidentWindow(1, None, {"bases": np.zeros(2)}, 2, 1, 2)
    rw2.retain()
    assert rw2.drop() is True     # drop ignores the refcount
    assert not rw2.alive
    assert rw2.drop() is False

    rw3 = dp.ResidentWindow(2, None, {"bases": np.zeros(2)}, 2, 1, 2)
    rw3.mark_consumed()
    assert not rw3.alive          # consumed handles stop offering args
    assert rw3.get("bases") is not None  # but buffers exist until release


# ---------------------------------------------------------------------------
# End-to-end: byte parity + ledger contract across the matrix
# ---------------------------------------------------------------------------
def _pin_fails():
    """Patches that make every ingest pin fail, so each pass places a
    transient handle (the transient placements run under their pass's
    own scope and still go through)."""
    real = dp.make_resident_window

    def place(b, window, device=None):
        if tele.current_pass() == "ingest":
            raise RuntimeError("injected ingest pin failure")
        return real(b, window, device)

    return [(dp, "make_resident_window", place)]


def _consumed_once(mesh: bool):
    """Patches that force buffer donation on and make the first
    (non-prewarm) pack2 dispatch consume its handle and then fail
    transiently — the retry must place a transient handle."""
    from adam_tpu.pipelines import bqsr as bq
    from adam_tpu.utils import compile_ledger
    from adam_tpu.utils.faults import TransientFault

    armed = [True]

    def fail_first():
        if armed[0] and not compile_ledger.in_prewarm():
            armed[0] = False
            raise TransientFault("injected failure after donation")

    if mesh:
        real_window = part_mod.MeshPartitioner.apply_pack2_window

        def window(self, *a, **kw):
            fail_first()
            return real_window(self, *a, **kw)

        return [
            (part_mod.MeshPartitioner, "apply_supports_donation",
             lambda self: True),
            (part_mod.MeshPartitioner, "apply_pack2_window", window),
        ]
    real_variant = bq.jit_variant

    def variant(kind, donate=False):
        fn = real_variant(kind, donate)
        if kind != "apply_pack2" or not donate:
            return fn

        def call(*a, **kw):
            fail_first()
            return fn(*a, **kw)

        return call

    return [
        (dp, "donation_ok", lambda device=None: True),
        (bq, "jit_variant", variant),
    ]


_FAST_RETRY = {
    "ADAM_TPU_RETRY_BACKOFF_S": "0.001",
    "ADAM_TPU_RETRY_ATTEMPTS": "2",
}


@pytest.fixture(scope="module")
def resident_runs(tmp_path_factory):
    """Streamed runs over one input (ragged last window + realign
    tail): the numpy host backend, pinned handles under the pool and
    mesh partitioners, a pool run whose ingest pins all fail (every
    pass on transient handles), and the fault legs — eviction in pass B
    and in pass C, a donating pack2 attempt that consumes its handle
    and fails (pool and mesh), and a mesh degraded to the pool mid-run
    — each with its telemetry snapshot and the compile-ledger kernel
    names it tracked."""
    import warnings

    from make_wgs_sam import make_wgs

    from adam_tpu.pipelines.streamed import transform_streamed
    from adam_tpu.utils import compile_ledger, faults

    d = tmp_path_factory.mktemp("resident")
    path = str(d / "in.sam")
    make_wgs(path, 4500, 100, n_contigs=2, contig_len=30_000,
             indel_every=700, snp_every=400)
    legs = [
        # label, partitioner, devices, extra env, patches
        ("host", None, None, {}, []),
        ("pool_off", "pool", 2, {}, _pin_fails()),
        ("pool_on", "pool", 2, {}, []),
        ("mesh_on", "mesh", 2, {}, []),
        ("pool_on_1dev", "pool", 1, {}, []),
        # a device dies mid-pass-B: its resident windows drop and the
        # replays place transient handles from the host-retained ingest
        # copy
        # after=1: arrival 2 on device 1 is window 1's pass-B observe
        ("pool_on_evict", "pool", 2, {
            "ADAM_TPU_FAULTS":
                "device.dispatch=permanent,device=1,after=1",
            **_FAST_RETRY,
        }, []),
        # device 1 dies at a fetch: at barrier 2 (its first pass-B
        # histogram) or in pass C (its first packed payload) — the
        # replay hooks evict it and recompute on the survivor
        ("pool_evict_b", "pool", 2, {
            "ADAM_TPU_FAULTS":
                "device.fetch=permanent,device=1,pass=observe",
            **_FAST_RETRY,
        }, []),
        ("pool_evict_c", "pool", 2, {
            "ADAM_TPU_FAULTS":
                "device.fetch=permanent,device=1,pass=apply",
            **_FAST_RETRY,
        }, []),
        ("pool_consumed", "pool", 2, _FAST_RETRY, _consumed_once(False)),
        ("mesh_consumed", "mesh", 2, _FAST_RETRY, _consumed_once(True)),
        # the mesh's first observe collective fails for good: the run
        # degrades to the pool, whose passes run on transient handles
        ("mesh_degrade", "mesh", 2, {
            "ADAM_TPU_FAULTS":
                "device.dispatch=permanent,pass=observe,times=1",
            **_FAST_RETRY,
        }, []),
    ]
    real_track = compile_ledger.track
    runs = {}
    for label, mode, n, extra, patches in legs:
        out = str(d / f"out.{label}.adam")
        old = {k: os.environ.get(k) for k in extra}
        os.environ.update(extra)
        if mode is not None:
            os.environ["ADAM_TPU_BQSR_BACKEND"] = "device"
        tracked: set = set()

        class _Recording(real_track):
            def __init__(self, key, device=None):
                tracked.add(key[0])
                super().__init__(key, device)

        saved = [(o, a, getattr(o, a)) for o, a, _v in patches]
        for o, a, v in patches:
            setattr(o, a, v)
        compile_ledger.track = _Recording
        # the spec env var is only read at import: arm in-process
        faults.install(extra.get("ADAM_TPU_FAULTS"))
        tele.TRACE.reset()
        tele.TRACE.recording = True
        try:
            with warnings.catch_warnings():
                # forced donation on the CPU runtime warns and copies
                warnings.simplefilter("ignore")
                stats = transform_streamed(
                    path, out, window_reads=2048, devices=n,
                    partitioner=mode,
                )
            snap = tele.TRACE.snapshot()
        finally:
            tele.TRACE.recording = False
            faults.install(None)
            compile_ledger.track = real_track
            for o, a, v in saved:
                setattr(o, a, v)
            os.environ.pop("ADAM_TPU_BQSR_BACKEND", None)
            for k, v in old.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v
        runs[label] = (out, stats, snap, tracked)
    return runs


_FAULT_LEGS = ("pool_on_evict", "pool_evict_b", "pool_evict_c",
               "pool_consumed", "mesh_consumed", "mesh_degrade")


def test_resident_parts_bit_identical_across_matrix(resident_runs):
    ref = _sha_parts(resident_runs["host"][0])
    assert ref
    for label in ("pool_off", "pool_on", "mesh_on", "pool_on_1dev",
                  *_FAULT_LEGS):
        assert _sha_parts(resident_runs[label][0]) == ref, label


def _assert_handles_balanced(c, snap):
    """Every placement, pinned or transient, left the registry: released
    after its pass (or part) or dropped by a fault, and no live bytes
    remain — no HBM growth across windows."""
    assert (
        c.get(tele.C_RESIDENT_RELEASED, 0)
        + c.get(tele.C_RESIDENT_EVICTED, 0)
        == c[tele.C_RESIDENT_WINDOWS]
    )
    assert snap["gauges"][tele.G_RESIDENT_LIVE]["last"] == 0


def test_resident_stats_and_counters(resident_runs):
    _, stats_on, snap_on, _ = resident_runs["pool_on"]
    _, stats_host, snap_host, _ = resident_runs["host"]
    _, stats_off, snap_off, _ = resident_runs["pool_off"]
    assert stats_on["resident_windows"] > 0
    assert stats_host["resident_windows"] == 0
    c_on = snap_on["counters"]
    assert c_on[tele.C_RESIDENT_WINDOWS] == stats_on["resident_windows"]
    assert c_on[tele.C_RESIDENT_BYTES] > 0
    # refcounted release-after-pass-C: every handle released, none
    # evicted, and the live-bytes gauge back at 0
    assert (
        c_on[tele.C_RESIDENT_RELEASED] == c_on[tele.C_RESIDENT_WINDOWS]
    )
    assert c_on.get(tele.C_RESIDENT_EVICTED, 0) == 0
    _assert_handles_balanced(c_on, snap_on)
    assert tele.C_RESIDENT_WINDOWS not in snap_host["counters"]
    # failed pins: one transient handle per pass that touches a window,
    # so more placements than the pinned run, all released
    c_off = snap_off["counters"]
    assert c_off[tele.C_RESIDENT_WINDOWS] > c_on[tele.C_RESIDENT_WINDOWS]
    assert c_off.get(tele.C_RESIDENT_EVICTED, 0) == 0
    _assert_handles_balanced(c_off, snap_off)
    # clean prewarm coverage on both device legs (donated-signature
    # executables dedupe against the prewarm)
    for snap in (snap_on, snap_off):
        in_window = [
            e for e in (snap.get("compiles", {}).get("entries") or [])
            if e.get("in_window")
        ]
        assert (
            snap["counters"].get(tele.C_COMPILE_IN_WINDOW, 0) == 0
        ), in_window


def _h2d_by_pass(snap):
    per = {}
    for _dev, passes in (snap.get("transfers", {}).get("h2d") or {}).items():
        for p, v in passes.items():
            per[p] = per.get(p, 0) + v["bytes"]
    return per


def test_resident_ledger_ingest_only(resident_runs):
    """The ledger contract: pinned handles collapse the per-pass h2d to
    one ingest entry per window — the observe and apply buckets drop to
    the per-pass scraps (bit-packed masks, validity bools), while a run
    whose pins failed places every window's rows again in each pass."""
    per_on = _h2d_by_pass(resident_runs["pool_on"][2])
    per_off = _h2d_by_pass(resident_runs["pool_off"][2])
    assert "ingest" in per_on and "ingest" not in per_off
    # the transient run ships the pinned run's per-pass scraps plus one
    # placement of every window's rows in pass B and again in pass C:
    # exactly the bytes the ingest pins placed once
    assert per_off["observe"] - per_on["observe"] == per_on["ingest"]
    assert per_off["apply"] - per_on["apply"] == per_on["ingest"]
    # observe h2d ≈ 0: the bit-packed masks are an eighth of the two
    # row matrices the pins hold
    assert 8 * per_on["observe"] < per_on["ingest"]
    dispatch_on = per_on["observe"] + per_on.get("apply", 0)
    assert dispatch_on < per_on["ingest"]
    # over the window-payload buckets (the table, resolve and sweep
    # traffic is the same on both legs) the pins move under half the
    # bytes the transient handles do
    payload = ("ingest", "a", "observe", "apply")
    total_on = sum(per_on.get(k, 0) for k in payload)
    total_off = sum(per_off.get(k, 0) for k in payload)
    assert total_on < total_off / 2


def test_resident_eviction_drops_handles(resident_runs):
    """The eviction leg: the dead device's resident windows dropped
    (device.resident.evicted > 0) and their replays re-shipped from
    the host copy — output byte-identity is asserted in the matrix
    test above."""
    _, stats, snap, _ = resident_runs["pool_on_evict"]
    c = snap["counters"]
    assert c.get(tele.C_DEVICE_EVICTED, 0) >= 1
    assert c.get(tele.C_RESIDENT_EVICTED, 0) > 0
    # every handle left the registry one way or the other
    _assert_handles_balanced(c, snap)


@pytest.mark.parametrize("label", ["pool_evict_b", "pool_evict_c"])
def test_eviction_replays_on_transient_handles(resident_runs, label):
    """A device evicted at a pass-B (or pass-C) fetch: its pinned
    handles drop, and the replay — and its windows' later dispatches —
    run on transient handles placed on the survivor, with the packed
    observe and pack2 programs the window would have run (the tracking
    test); more placements than the clean run, all balanced, output
    byte-identical (the matrix test)."""
    _, _stats, snap, _ = resident_runs[label]
    c = snap["counters"]
    c_on = resident_runs["pool_on"][2]["counters"]
    assert c.get(tele.C_DEVICE_EVICTED, 0) == 1
    assert c.get(tele.C_RESIDENT_EVICTED, 0) > 0
    assert c[tele.C_RESIDENT_WINDOWS] > c_on[tele.C_RESIDENT_WINDOWS]
    assert snap["spans"][tele.SPAN_POOL_REPLAY]["count"] >= 1
    _assert_handles_balanced(c, snap)


@pytest.mark.parametrize("label", ["pool_consumed", "mesh_consumed"])
def test_consumed_handle_retries_pack2_on_transient(resident_runs, label):
    """A donating pack2 attempt consumes its handle and fails: the retry
    places ONE transient handle and dispatches pack2 again — no device
    is evicted, nothing degrades, and the window still comes home
    packed (output byte-identity is the matrix test's)."""
    _, stats, snap, tracked = resident_runs[label]
    c = snap["counters"]
    on = "mesh_on" if label.startswith("mesh") else "pool_on"
    c_on = resident_runs[on][2]["counters"]
    assert c.get(tele.C_FAULT_INJECTED, 0) == 0  # not a faults.point
    assert c.get(tele.C_RETRY_ATTEMPTS, 0) >= 1
    assert c.get(tele.C_DEVICE_EVICTED, 0) == 0
    assert c.get(tele.C_MESH_DEGRADED, 0) == 0
    assert stats["partitioner"] == resident_runs[on][1]["partitioner"]
    assert (
        c[tele.C_RESIDENT_WINDOWS] == c_on[tele.C_RESIDENT_WINDOWS] + 1
    )
    assert c.get(tele.C_RESIDENT_EVICTED, 0) == 0
    _assert_handles_balanced(c, snap)
    pack2 = "mesh.apply_pack2" if label.startswith("mesh") else \
        "bqsr.apply_pack2"
    assert pack2 in tracked


def test_mesh_degrade_runs_pool_passes_on_handles(resident_runs):
    """The mesh degrades to the pool mid-run: its mesh-sharded pins
    drop (counted evicted) and every later pool dispatch runs on a
    transient handle — the plain re-ship kernels never run."""
    _, stats, snap, tracked = resident_runs["mesh_degrade"]
    c = snap["counters"]
    assert c.get(tele.C_MESH_DEGRADED, 0) == 1
    assert stats["partitioner"] == "pool"
    assert c.get(tele.C_RESIDENT_EVICTED, 0) > 0
    assert (
        c[tele.C_RESIDENT_WINDOWS]
        > resident_runs["mesh_on"][2]["counters"][tele.C_RESIDENT_WINDOWS]
    )
    _assert_handles_balanced(c, snap)
    assert {"bqsr.observe_packed", "bqsr.apply_pack2"} <= tracked


#: The one program per pass a streamed device run may compile or
#: dispatch: markdup columns, the bit-packed observe, the pack2 apply
#: and the fused B→C megakernel, on either partitioner.
_STREAMED_KERNELS = frozenset({
    "markdup.columns", "bqsr.observe_packed", "bqsr.apply_pack2",
    "bqsr.fused_bc", "mesh.markdup", "mesh.observe_packed",
    "mesh.apply_pack2", "mesh.fused_bc",
})


@pytest.mark.parametrize("label", [
    "pool_on", "mesh_on", "pool_on_1dev", "pool_off", *_FAULT_LEGS,
])
def test_streamed_run_tracks_only_one_program_per_pass(resident_runs,
                                                       label):
    """The compile-ledger keys a streamed run tracks (prewarm and
    dispatch alike, replays included) name only the per-pass programs:
    no plain observe, plain gather or quals-only pack comes back
    unnoticed."""
    tracked = resident_runs[label][3]
    bqsr_keys = {
        k for k in tracked if k.split(".")[0] in ("bqsr", "mesh", "markdup")
    }
    assert bqsr_keys <= _STREAMED_KERNELS, bqsr_keys - _STREAMED_KERNELS
    mesh = label in ("mesh_on", "mesh_consumed")
    assert ("mesh.apply_pack2" if mesh else "bqsr.apply_pack2") in tracked
    assert (
        "mesh.observe_packed" if mesh else "bqsr.observe_packed"
    ) in tracked


def test_analyzer_residency_section(resident_runs):
    from adam_tpu.utils import analyzer

    rep_on = analyzer.analyze(resident_runs["pool_on"][2])
    res = rep_on["residency"]
    assert res["windows"] > 0 and res["bytes"] > 0
    assert res["ingest_only"] is True
    assert "ingest" in res["h2d_by_pass"]
    assert res["donated_compiles"]["in_window"] == 0
    text = analyzer.render_report(rep_on)
    assert "Device residency" in text and "ingest-only" in text
    # the host leg renders no residency section
    rep_host = analyzer.analyze(resident_runs["host"][2])
    assert rep_host["residency"] == {}


def test_packed_columns_take_and_arrow():
    """PackedColumns row-subset + zero-copy sequence column parity
    against the host LUT path."""
    import pyarrow as pa

    from adam_tpu.formats import schema
    from adam_tpu.io.arrow_pack import (
        PackedColumns, PackedQuals, packed_base_array,
    )
    from adam_tpu.ops.colpack import pack_rows_np

    rng = np.random.default_rng(2)
    n, L = 40, 24
    bases = rng.integers(0, 6, (n, L)).astype(np.uint8)
    lengths = rng.integers(1, L, n).astype(np.int64)
    valid = rng.random(n) < 0.8
    b_lens = np.where(valid, lengths, 0)
    packed = PackedColumns(
        quals=PackedQuals(np.zeros(0, np.uint8), np.zeros(n, np.int64)),
        bases=PackedQuals(
            pack_rows_np(schema.BASE_DECODE_LUT256[bases], b_lens),
            b_lens,
        ),
    )
    rows = np.flatnonzero(valid)
    taken = packed.take(rows)
    got = packed_base_array(taken.bases)
    dec = schema.BASE_DECODE_LUT256[bases]
    want = pa.array(
        [dec[i, : lengths[i]].tobytes().decode("ascii") for i in rows],
        pa.large_string(),
    )
    assert got.cast(pa.large_string()).equals(want)


# ---------------------------------------------------------------------------
# SIGKILL mid-pass-B on the resident path, then --resume
# ---------------------------------------------------------------------------
_KILL_DRIVER = (
    "import sys\n"
    "from adam_tpu.pipelines.streamed import transform_streamed\n"
    "transform_streamed(sys.argv[1], sys.argv[2], window_reads=512,\n"
    "                   devices=2,\n"
    "                   run_dir=sys.argv[3], resume=sys.argv[4] == '1')\n"
)


def test_resident_sigkill_mid_pass_b_then_resume(tmp_path):
    """SIGKILL a resident --devices 2 run at the mid-pass-B phase
    boundary (device-resident windows in flight, nothing persisted),
    then --resume: byte-identical to an uninterrupted run."""
    from make_wgs_sam import make_wgs

    from adam_tpu.pipelines.streamed import transform_streamed

    path = str(tmp_path / "in.sam")
    make_wgs(path, 2000, 100, n_contigs=2, contig_len=20_000,
             indel_every=700, snp_every=400)
    clean = str(tmp_path / "clean.adam")
    transform_streamed(path, clean, window_reads=512)
    baseline = _sha_parts(clean)
    assert baseline

    out, rd = str(tmp_path / "out.adam"), str(tmp_path / "run")
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": (env.get("XLA_FLAGS", "")
                      + " --xla_force_host_platform_device_count=2"),
        "ADAM_TPU_NO_COMPILE_CACHE": "1",
        "ADAM_TPU_BQSR_BACKEND": "device",
        "ADAM_TPU_FAULTS": "proc.kill=kill,device=pass_b,after=1,times=1",
    })
    cwd = os.path.join(os.path.dirname(__file__), "..")
    rc = subprocess.run(
        [sys.executable, "-c", _KILL_DRIVER, path, out, rd, "0"],
        env=env, cwd=cwd,
    ).returncode
    assert rc == -signal.SIGKILL, f"expected SIGKILL, got {rc}"
    env.pop("ADAM_TPU_FAULTS")
    rc = subprocess.run(
        [sys.executable, "-c", _KILL_DRIVER, path, out, rd, "1"],
        env=env, cwd=cwd,
    ).returncode
    assert rc == 0
    assert _sha_parts(out) == baseline
