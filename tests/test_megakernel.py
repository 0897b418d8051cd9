"""Megakernel tier (ISSUE 18, docs/PERF.md "Megakernel tier"): the
fused B→C dispatch.

The matrix this file owes the acceptance criteria:

* toggle parsing — `ADAM_TPU_FUSED_BC` through the shared env_toggle;
* kernel-level bit parity — `fused_bc_body` vs the separate
  observe_packed + apply_pack2 passes (including the wider merged-table
  geometry);
* end-to-end byte parity of known-table runs, fused vs unfused, across
  pool/mesh and 1/2/8 virtual devices, with the dispatch-count factor
  (≥ 1.5x), `device.windows.fused` and the `streamed.fused_bc` gauge
  asserted, `device.compile.in_window == 0`;
* the fault matrix — eviction mid-fused-dispatch replays through the
  split chain byte-identically, and a SIGKILL mid-fused run resumes
  byte-identically (`proc.kill device=fused_bc`);
* the kernelbench schema (`adam_tpu.kernelbench/1`) and the analyzer's
  merged `fused_bc_apply` stage row.
"""

import hashlib
import os
import signal
import subprocess
import sys

import numpy as np
import pytest

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
# Toggle parsing
# ---------------------------------------------------------------------------
def test_fused_bc_toggle_parsing(monkeypatch):
    from adam_tpu.pipelines.bqsr import fused_bc_enabled

    monkeypatch.delenv("ADAM_TPU_FUSED_BC", raising=False)
    assert fused_bc_enabled() is True
    assert fused_bc_enabled(default=False) is False
    for raw, want in (("1", True), ("on", True), ("0", False),
                      ("off", False), ("auto", True)):
        monkeypatch.setenv("ADAM_TPU_FUSED_BC", raw)
        assert fused_bc_enabled() is want, raw


# ---------------------------------------------------------------------------
# Kernel-level bit parity: fused vs separate
# ---------------------------------------------------------------------------
def _fused_inputs(seed=5, g=48, gl=40, n_rg=3, n_cyc=None):
    from adam_tpu.ops.colpack import pack_mask_bits

    rng = np.random.default_rng(seed)
    return dict(
        g=g, gl=gl, n_rg=n_rg,
        bases=rng.integers(0, 6, (g, gl)).astype(np.uint8),
        quals=rng.integers(0, 60, (g, gl)).astype(np.uint8),
        lengths=rng.integers(1, gl, g).astype(np.int32),
        flags=rng.integers(0, 4, g).astype(np.int32),
        rg=rng.integers(-1, n_rg - 1, g).astype(np.int32),
        res_bits=pack_mask_bits(rng.random((g, gl)) < 0.6),
        mm_bits=pack_mask_bits(rng.random((g, gl)) < 0.2),
        read_ok=rng.random(g) < 0.8,
        has_qual=rng.random(g) < 0.9,
        valid=rng.random(g) < 0.95,
        table=rng.integers(
            2, 43, (n_rg, 94, n_cyc or 2 * gl + 1, 17)
        ).astype(np.uint8),
    )


def _run_fused(k):
    from adam_tpu.pipelines.bqsr import jit_variant

    size = k["g"] * k["gl"]
    return tuple(
        np.asarray(a) for a in jit_variant("fused_bc", False)(
            k["bases"], k["quals"], k["lengths"], k["flags"], k["rg"],
            k["res_bits"], k["mm_bits"], k["read_ok"], k["has_qual"],
            k["valid"], k["table"], k["n_rg"], k["gl"], size,
        )
    )


def _run_separate(k):
    from adam_tpu.pipelines.bqsr import jit_variant

    size = k["g"] * k["gl"]
    total, mism = jit_variant("observe_packed", False)(
        k["bases"], k["quals"], k["lengths"], k["flags"], k["rg"],
        k["res_bits"], k["mm_bits"], k["read_ok"], k["n_rg"], k["gl"],
    )
    pq, pb = jit_variant("apply_pack2", False)(
        k["bases"], k["quals"], k["lengths"], k["flags"], k["rg"],
        k["has_qual"], k["valid"], k["table"], k["gl"], size,
    )
    return tuple(np.asarray(a) for a in (total, mism, pq, pb))


def test_fused_bc_kernel_bit_parity():
    """The megakernel is a pure composition: its four outputs are
    bitwise the separate observe + apply_pack2 outputs."""
    k = _fused_inputs()
    for got, want in zip(_run_fused(k), _run_separate(k)):
        np.testing.assert_array_equal(got, want)
    assert int(_run_fused(k)[0].sum()) > 0  # a real workload


def test_fused_bc_wider_table_parity():
    """Known-sites tables carry the COHORT's cycle-axis width, wider
    than this window's — the centered gather must agree with the
    separate apply against the same wide table."""
    k = _fused_inputs(seed=9, gl=32, n_cyc=2 * 48 + 1)
    for got, want in zip(_run_fused(k), _run_separate(k)):
        np.testing.assert_array_equal(got, want)


def test_kernelbench_schema():
    """The microbench document: stable schema, every kernel timed."""
    from adam_tpu.utils.kernelbench import (
        KERNELS, SCHEMA, run_kernelbench,
    )

    doc = run_kernelbench(grids=((32, 32),), iters=1)
    assert doc["schema"] == SCHEMA
    rows = doc["rows"]
    assert [r["kernel"] for r in rows] == list(KERNELS)
    for r in rows:
        assert r["mean_s"] >= r["best_s"] > 0, r


# ---------------------------------------------------------------------------
# End-to-end: known-table byte parity + the dispatch-count factor
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def megakernel_runs(tmp_path_factory):
    """One input, one discovered table, then known-table streamed runs:
    unfused (the A/B reference), fused across pool/mesh/1-dev/8-dev,
    and an eviction-mid-fused leg."""
    from make_wgs_sam import make_wgs

    from adam_tpu.pipelines.streamed import transform_streamed

    d = tmp_path_factory.mktemp("megakernel")
    path = str(d / "in.sam")
    make_wgs(path, 4500, 100, n_contigs=2, contig_len=30_000,
             indel_every=700, snp_every=400)

    from adam_tpu.utils import faults

    runs = {}

    def leg(label, mode, n, fused, extra=None, known=None):
        out = str(d / f"out.{label}.adam")
        env_keys = {"ADAM_TPU_FUSED_BC": fused, **(extra or {})}
        old = {k: os.environ.get(k) for k in env_keys}
        os.environ.update(env_keys)
        if mode is not None:
            os.environ["ADAM_TPU_BQSR_BACKEND"] = "device"
        faults.install((extra or {}).get("ADAM_TPU_FAULTS"))
        tele.TRACE.reset()
        tele.TRACE.recording = True
        try:
            stats = transform_streamed(
                path, out, window_reads=2048, devices=n,
                partitioner=mode, known_table=known,
                run_dir=str(d / f"rd.{label}"),
            )
            snap = tele.TRACE.snapshot()
        finally:
            tele.TRACE.recording = False
            faults.install(None)
            os.environ.pop("ADAM_TPU_BQSR_BACKEND", None)
            for k, v in old.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v
        runs[label] = (out, stats, snap)

    # the discovered-table reference (no table at ingest: never fuses)
    leg("discover", "pool", 2, "1")
    with np.load(str(d / "rd.discover" / "table.npz")) as z:
        known = (np.asarray(z["table"], np.uint8), int(z["gl"]))

    leg("unfused", "pool", 2, "0", known=known)
    leg("fused_pool", "pool", 2, "1", known=known)
    leg("fused_mesh", "mesh", 2, "1", known=known)
    leg("fused_1dev", "pool", 1, "1", known=known)
    leg("fused_8dev", "pool", 8, "1", known=known)
    # a device dies mid-fused-dispatch: the resident handle drops and
    # the replay falls back to the split chain from the host copy
    leg("fused_evict", "pool", 2, "1", known=known, extra={
        "ADAM_TPU_FAULTS": "device.dispatch=permanent,device=1,after=1",
        "ADAM_TPU_RETRY_BACKOFF_S": "0.001",
        "ADAM_TPU_RETRY_ATTEMPTS": "2",
    })
    return runs


def test_megakernel_parts_bit_identical_across_matrix(megakernel_runs):
    ref = _sha_parts(megakernel_runs["unfused"][0])
    assert ref
    for label in ("discover", "fused_pool", "fused_mesh", "fused_1dev",
                  "fused_8dev", "fused_evict"):
        assert _sha_parts(megakernel_runs[label][0]) == ref, label


def test_megakernel_dispatch_factor(megakernel_runs):
    """The tier's headline: fused known-table runs dispatch ≥ 1.5x
    fewer per-window device calls than the unfused chain, with every
    fused window counted and zero in-window cold compiles."""
    _, st_u, sn_u = megakernel_runs["unfused"]
    _, st_f, sn_f = megakernel_runs["fused_pool"]
    assert st_f["fused_bc"] is True
    assert st_u["fused_bc"] is False
    assert megakernel_runs["discover"][1]["fused_bc"] is False
    c_u, c_f = sn_u["counters"], sn_f["counters"]
    assert c_f.get(tele.C_FUSED_DISPATCHED, 0) > 0
    assert tele.C_FUSED_DISPATCHED not in c_u
    d_u = c_u[tele.C_DEVICE_DISPATCHED]
    d_f = c_f[tele.C_DEVICE_DISPATCHED]
    assert d_u / d_f >= 1.5, (d_u, d_f)
    for label in ("unfused", "fused_pool", "fused_mesh", "fused_8dev"):
        snap = megakernel_runs[label][2]
        assert snap["counters"].get(
            tele.C_COMPILE_IN_WINDOW, 0
        ) == 0, label
    assert sn_f["gauges"][tele.G_FUSED_BC]["last"] == 1
    assert sn_u["gauges"][tele.G_FUSED_BC]["last"] == 0


def test_megakernel_mesh_counts_fused(megakernel_runs):
    _, st_m, sn_m = megakernel_runs["fused_mesh"]
    assert st_m["fused_bc"] is True
    c = sn_m["counters"]
    assert c.get(tele.C_FUSED_DISPATCHED, 0) > 0
    assert c.get(tele.C_MESH_DISPATCHED, 0) > 0


def test_megakernel_eviction_falls_back_to_split(megakernel_runs):
    """Byte-identity is asserted in the matrix test; here the shape of
    the recovery: the chip evicted, its windows' fused handles gone,
    and the run still finished (replayed windows take the split
    chain — fused_bc_dispatch declines a dead resident handle)."""
    _, stats, snap = megakernel_runs["fused_evict"]
    c = snap["counters"]
    assert c.get(tele.C_DEVICE_EVICTED, 0) >= 1
    assert stats["fused_bc"] is True


def test_analyzer_merges_fused_stage(megakernel_runs):
    """`adam-tpu analyze` on a fused run renders observe + pass-C apply
    as ONE `fused_bc_apply` stage row (the two spans no longer describe
    disjoint dispatch chains); fractions still sum against run wall."""
    from adam_tpu.utils import analyzer

    rep_f = analyzer.analyze(megakernel_runs["fused_pool"][2])
    stages_f = rep_f["stages"]
    assert "fused_bc_apply" in stages_f
    assert "observe" not in stages_f and "pass_c_apply" not in stages_f
    assert stages_f["fused_bc_apply"]["total_s"] >= 0
    fracs = [
        row.get("frac") for row in stages_f.values()
        if isinstance(row, dict) and row.get("frac") is not None
    ]
    assert fracs and sum(fracs) <= 1.05
    rep_u = analyzer.analyze(megakernel_runs["unfused"][2])
    assert "fused_bc_apply" not in rep_u["stages"]
    assert "observe" in rep_u["stages"]


# ---------------------------------------------------------------------------
# SIGKILL mid-fused-dispatch, then --resume
# ---------------------------------------------------------------------------
_KILL_DRIVER = (
    "import sys\n"
    "import numpy as np\n"
    "from adam_tpu.pipelines.streamed import transform_streamed\n"
    "with np.load(sys.argv[5]) as z:\n"
    "    known = (np.asarray(z['table'], np.uint8), int(z['gl']))\n"
    "transform_streamed(sys.argv[1], sys.argv[2], window_reads=512,\n"
    "                   devices=2, known_table=known,\n"
    "                   run_dir=sys.argv[3], resume=sys.argv[4] == '1')\n"
)


@pytest.mark.slow
def test_megakernel_sigkill_mid_fused_then_resume(tmp_path):
    """SIGKILL at the fused-dispatch fault point (`proc.kill
    device=fused_bc`) with windows in flight, then --resume:
    byte-identical to an uninterrupted unfused run."""
    from make_wgs_sam import make_wgs

    from adam_tpu.pipelines.streamed import transform_streamed

    path = str(tmp_path / "in.sam")
    make_wgs(path, 2000, 100, n_contigs=2, contig_len=20_000,
             indel_every=700, snp_every=400)
    # discover the table, then an unfused known-table baseline
    disc = str(tmp_path / "disc.adam")
    transform_streamed(path, disc, window_reads=512,
                       run_dir=str(tmp_path / "rd.disc"))
    table_npz = str(tmp_path / "rd.disc" / "table.npz")
    with np.load(table_npz) as z:
        known = (np.asarray(z["table"], np.uint8), int(z["gl"]))
    clean = str(tmp_path / "clean.adam")
    old = os.environ.get("ADAM_TPU_FUSED_BC")
    os.environ["ADAM_TPU_FUSED_BC"] = "0"
    try:
        transform_streamed(path, clean, window_reads=512,
                           known_table=known)
    finally:
        if old is None:
            os.environ.pop("ADAM_TPU_FUSED_BC", None)
        else:
            os.environ["ADAM_TPU_FUSED_BC"] = old
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
        "ADAM_TPU_FUSED_BC": "1",
        "ADAM_TPU_FAULTS":
            "proc.kill=kill,device=fused_bc,after=1,times=1",
    })
    cwd = os.path.join(os.path.dirname(__file__), "..")
    rc = subprocess.run(
        [sys.executable, "-c", _KILL_DRIVER, path, out, rd, "0",
         table_npz],
        env=env, cwd=cwd,
    ).returncode
    assert rc == -signal.SIGKILL, f"expected SIGKILL, got {rc}"
    env.pop("ADAM_TPU_FAULTS")
    rc = subprocess.run(
        [sys.executable, "-c", _KILL_DRIVER, path, out, rd, "1",
         table_npz],
        env=env, cwd=cwd,
    ).returncode
    assert rc == 0
    assert _sha_parts(out) == baseline
