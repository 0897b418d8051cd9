#!/usr/bin/env python3
"""Chip smoke: drive the streamed flagship ``transform`` once on a TPU.

One process, run from the repo root on a machine with a chip::

    python chip_smoke.py              # one chip: the phases below
    python chip_smoke.py --chips 4    # four chips: --devices 4 pool/mesh
                                      # against --devices 1, nothing else

Phases (one chip):

1. device gate — the default platform must be ``tpu`` (no CPU fallback)
   and the native C++ library must build and load;
2. flagship — a 1M-read WGS-shaped SAM (``tools/make_wgs_sam``, seed 0,
   4 contigs x 800 kb, known sites) through ``adam_tpu.cli.main``
   ``transform -streaming`` with markdup, realign and BQSR, then
   ``flagstat`` on the output, whose total must equal the input count;
3. reference — the first 262,144 reads through ``transform_streamed``
   with ``ADAM_TPU_BQSR_BACKEND=device`` and ``=numpy``: the Parquet
   parts must be byte-identical;
4. Smith-Waterman — the compiled Pallas score and fill kernels at
   B=8192, 128x128 must equal the ``lax.scan`` fills.

Any sign that the device was bypassed fails the run: a BQSR backend
other than ``device``, an eviction, a mesh degrade, a lost device, a
host-backend fallback logged by the pipeline, or a duplicate-resolve
sort that fell back to the host.  Information lines go to
stdout as ``chip_smoke: ...``; the last line is the JSON result, printed
only when every phase passed.
"""

from __future__ import annotations

import argparse
import contextlib
import hashlib
import io
import json
import logging
import os
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tools"))

N_READS = 1_000_000
READ_LEN = 100
WINDOW = 262_144
SW_B, SW_L = 8192, 128


class SmokeFailure(RuntimeError):
    pass


def say(msg: str) -> None:
    print(f"chip_smoke: {msg}", flush=True)


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


# ---------------------------------------------------------------- gate


def device_gate(need: int) -> dict:
    import jax
    import jaxlib
    from importlib import metadata

    devs = jax.devices()
    d0 = devs[0]
    check(d0.platform == "tpu",
          f"default platform is {d0.platform!r}, not 'tpu'")
    check(len(devs) >= need, f"{need} chip(s) needed, {len(devs)} attached")
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "not installed as a package"
    say(f"device {d0.device_kind} x{len(devs)}; jax {jax.__version__}, "
        f"jaxlib {jaxlib.__version__}, libtpu {libtpu}")

    from adam_tpu import native  # places the compile cache, enables x64

    say(f"compile cache: {jax.config.jax_compilation_cache_dir}")
    loaded = native._lib() is not None
    say(f"native library loaded: {loaded}")
    check(loaded, "native C++ library did not build/load")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs)}


# -------------------------------------------------------------- inputs


def make_inputs(td: str) -> tuple:
    from make_wgs_sam import make_wgs

    sam = os.path.join(td, "wgs.sam")
    known = os.path.join(td, "known.vcf")
    t0 = time.perf_counter()
    make_wgs(sam, N_READS, READ_LEN, seed=0, known_sites_out=known)
    say(f"input: {N_READS} reads x {READ_LEN} bp in "
        f"{time.perf_counter() - t0:.1f} s (set-up)")
    return sam, known


def sam_head(sam: str, out: str, n: int) -> None:
    """First ``n`` records of ``sam`` (header kept) into ``out``."""
    got = 0
    with open(sam) as src, open(out, "w") as dst:
        for line in src:
            if not line.startswith("@"):
                if got == n:
                    break
                got += 1
            dst.write(line)


def count_records(sam: str) -> int:
    with open(sam, "rb") as fh:
        return sum(1 for line in fh if not line.startswith(b"@"))


def parts_hash(d: str) -> dict:
    return {
        f: hashlib.sha256(open(os.path.join(d, f), "rb").read()).hexdigest()
        for f in sorted(os.listdir(d)) if f.startswith("part-")
    }


def same_parts(a: str, b: str, what: str) -> None:
    ha, hb = parts_hash(a), parts_hash(b)
    check(bool(ha), f"{what}: {a} wrote no parts")
    if ha == hb:
        return
    import pyarrow.parquet as pq

    cols = []
    if sorted(ha) == sorted(hb):
        for f in ha:
            if ha[f] == hb[f]:
                continue
            ta = pq.read_table(os.path.join(a, f))
            tb = pq.read_table(os.path.join(b, f))
            cols += [f"{f}:{c}" for c in ta.column_names
                     if c not in tb.column_names
                     or not ta[c].equals(tb[c])]
    raise SmokeFailure(
        f"{what}: parts differ (files {sorted(ha)} vs {sorted(hb)}; "
        f"columns {cols[:12]})"
    )


# ----------------------------------------------------- fallback guards


class _ErrorLog(logging.Handler):
    """Collects ERROR records from the package's loggers: the pipeline
    logs every eviction, mesh degrade and host-backend fallback there."""

    def __init__(self):
        super().__init__(level=logging.ERROR)
        self.records = []

    def emit(self, record):
        self.records.append(record.getMessage())


@contextlib.contextmanager
def error_log():
    h = _ErrorLog()
    lg = logging.getLogger("adam_tpu")
    lg.addHandler(h)
    try:
        yield h.records
    finally:
        lg.removeHandler(h)


_FAULT_COUNTERS = ("device.evicted", "device.mesh.degraded",
                   "device.resident.evicted")


def cli_transform(sam: str, known: str, out: str, metrics: str,
                  extra: tuple = ()) -> dict:
    """``adam-tpu transform -streaming`` in this process; returns the
    metrics snapshot after checking it for hidden fallbacks."""
    from adam_tpu.cli.main import main
    from adam_tpu.pipelines import bqsr
    from adam_tpu.utils import telemetry as tele

    tele.TRACE.reset()
    argv = ["transform", sam, out, "-streaming", "-mark_duplicate_reads",
            "-realign_indels", "-recalibrate_base_qualities",
            "-known_snps", known, "--metrics-json", metrics, *extra]
    with error_log() as errors:
        t0 = time.perf_counter()
        rc = main(argv)
        wall = time.perf_counter() - t0
    check(rc == 0, f"transform {' '.join(extra)} exited {rc}")
    check(not errors, f"pipeline logged errors: {errors[:3]}")
    backend = bqsr.bqsr_backend()
    check(backend == "device", f"BQSR backend resolved to {backend!r}")
    with open(metrics) as fh:
        snap = json.load(fh)
    c = snap["counters"]
    for name in _FAULT_COUNTERS:
        check(not c.get(name), f"{name} = {c.get(name)}")
    # the resolve lexsort falls back to the host with only a warning
    sort = snap["gauges"].get("streamed.resolve.device_sort", {})
    check(sort.get("last") == 1, "duplicate-resolve sort ran on the host")
    check(c.get("reads.ingested") == N_READS,
          f"ingested {c.get('reads.ingested')} reads, want {N_READS}")
    check(c.get("device.h2d.bytes", 0) > 0, "no h2d bytes: device unused")
    snap["wall_s"] = wall
    return snap


def report(snap: dict, label: str) -> None:
    import jax

    c = snap["counters"]
    wall = snap["wall_s"]
    comp = snap.get("histograms", {}).get("device.compile.seconds", {})
    say(f"{label}: wall {wall} s, {N_READS / wall} reads/s, "
        f"ledger-tracked compile {comp.get('sum', 0.0)} s over "
        f"{comp.get('count', 0)} programs, retries "
        f"{c.get('retry.attempts', 0)}")
    stages = {k: v["total_s"] for k, v in snap["spans"].items()
              if k.startswith("streamed.")}
    say(f"{label}: stage walls {json.dumps(stages)}")
    say(f"{label}: h2d {c.get('device.h2d.bytes', 0)} B, "
        f"d2h {c.get('device.d2h.bytes', 0)} B")
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()]
    say(f"{label}: peak_bytes_in_use {peaks}")


def flagstat_total(out: str) -> int:
    from adam_tpu.cli.main import main

    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = main(["flagstat", out])
    check(rc == 0, f"flagstat exited {rc}")
    first = buf.getvalue().splitlines()[0]
    say(f"flagstat: {first}")
    passed, _, failed = first.split()[:3]
    return int(passed) + int(failed)


# --------------------------------------------------------------- phases


def phase_flagship(td: str, sam: str, known: str) -> None:
    out = os.path.join(td, "out.adam")
    snap = cli_transform(sam, known, out, os.path.join(td, "m.json"))
    report(snap, "flagship")
    n_sam = count_records(sam)
    total = flagstat_total(out)
    check(total == n_sam, f"flagstat total {total} != SAM records {n_sam}")


def phase_reference(td: str, sam: str, known: str) -> None:
    """Device BQSR against the plain host reference on one window."""
    from adam_tpu.api.datasets import GenotypeDataset
    from adam_tpu.io import context
    from adam_tpu.pipelines.streamed import transform_streamed

    head = os.path.join(td, "head.sam")
    sam_head(sam, head, WINDOW)
    names = context.load_header(head).seq_dict.names
    table = GenotypeDataset.load(known, contig_names=names).snp_table()
    outs = {}
    prev = os.environ.get("ADAM_TPU_BQSR_BACKEND")
    try:
        for backend in ("device", "numpy"):
            os.environ["ADAM_TPU_BQSR_BACKEND"] = backend
            outs[backend] = os.path.join(td, f"ref.{backend}.adam")
            with error_log() as errors:
                t0 = time.perf_counter()
                st = transform_streamed(head, outs[backend],
                                        known_snps=table)
            check(not errors, f"{backend} run logged errors: {errors[:3]}")
            check(st["bqsr_backend"] == backend,
                  f"asked for {backend}, ran {st['bqsr_backend']}")
            say(f"reference: {st['n_reads']} reads, bqsr={backend}, "
                f"{time.perf_counter() - t0:.2f} s")
    finally:
        if prev is None:
            os.environ.pop("ADAM_TPU_BQSR_BACKEND", None)
        else:
            os.environ["ADAM_TPU_BQSR_BACKEND"] = prev
    same_parts(outs["device"], outs["numpy"], "device vs numpy BQSR")
    say(f"reference: {len(parts_hash(outs['device']))} parts "
        "byte-identical (device vs numpy)")


def phase_smith_waterman() -> None:
    import jax.numpy as jnp
    import numpy as np

    from adam_tpu.ops import smith_waterman as sw

    rng = np.random.default_rng(0)
    xc = jnp.asarray(rng.integers(0, 4, (SW_B, SW_L)), jnp.int32)
    yc = jnp.asarray(rng.integers(0, 4, (SW_B, SW_L)), jnp.int32)
    xl = jnp.asarray(rng.integers(SW_L // 2, SW_L + 1, SW_B), jnp.int32)
    yl = jnp.asarray(rng.integers(SW_L // 2, SW_L + 1, SW_B), jnp.int32)
    w = (1.0, -0.333, -0.5, -0.5)

    got = np.asarray(sw.sw_best_scores(xc, xl, yc, yl, *w,
                                       backend="pallas"))
    want = np.asarray(sw.sw_best_scores(xc, xl, yc, yl, *w,
                                        backend="scan"))
    check(got.shape == (SW_B,) and np.isfinite(got).all(),
          "pallas SW scores not finite/shaped")
    check(np.array_equal(got, want),
          f"pallas SW scores differ from scan in "
          f"{int((got != want).sum())} of {SW_B} pairs")

    m_p, bs_p, bd_p = sw._sw_fill_pallas(xc, xl, yc, yl, SW_L, SW_L, *w,
                                         interpret=False)
    m_s, bs_s, bd_s = sw._sw_fill_scan_best(xc, xl, yc, yl, *w, SW_L, SW_L)
    check(np.array_equal(np.asarray(m_p), np.asarray(m_s)),
          "pallas SW moves differ from scan")
    bs_s = np.asarray(bs_s)
    check(np.array_equal(np.asarray(bs_p), bs_s),
          "pallas SW best scores differ from scan")
    fin = np.isfinite(bs_s)
    check(np.array_equal(np.asarray(bd_p)[fin], np.asarray(bd_s)[fin]),
          "pallas SW best diagonals differ from scan")
    say(f"smith-waterman: pallas score + fill == scan at B={SW_B}, "
        f"{SW_L}x{SW_L}")


def phase_four_chips(td: str, sam: str, known: str) -> None:
    """``--devices 4`` under pool and mesh against ``--devices 1``."""
    runs = (("pool4", ("--devices", "4", "--partitioner", "pool")),
            ("mesh4", ("--devices", "4", "--partitioner", "mesh")),
            ("one", ("--devices", "1")))
    outs = {}
    for label, extra in runs:
        outs[label] = os.path.join(td, f"{label}.adam")
        snap = cli_transform(sam, known, outs[label],
                             os.path.join(td, f"{label}.json"), extra)
        report(snap, label)
        if label == "one":
            continue
        per_dev = {}
        for kind in ("h2d", "d2h"):
            for dev, buckets in snap["transfers"][kind].items():
                per_dev.setdefault(dev, {})[kind] = sum(
                    b["bytes"] for b in buckets.values())
        say(f"{label}: per-device transfer bytes {json.dumps(per_dev)}")
        # mesh fetches land under "mesh"; every chip must still be fed
        busy = [d for d, v in per_dev.items()
                if d.isdigit() and sum(v.values()) > 0]
        check(len(busy) >= 4, f"{label}: only devices {busy} moved bytes")
    for label in ("pool4", "mesh4"):
        same_parts(outs[label], outs["one"], f"{label} vs 1 device")
    say(f"four chips: pool, mesh and 1 device wrote "
        f"{len(parts_hash(outs['one']))} byte-identical parts")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    device = device_gate(args.chips)
    with tempfile.TemporaryDirectory(prefix="chip_smoke.") as td:
        sam, known = make_inputs(td)
        if args.chips == 4:
            phase_four_chips(td, sam, known)
        else:
            phase_flagship(td, sam, known)
            phase_reference(td, sam, known)
            phase_smith_waterman()
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr)
        sys.exit(1)
