"""Benchmark: BASELINE.md configs on the real chip.

Primary metric — **end-to-end transform throughput**: a 1M-read,
WGS-shaped SAM file (multi-contig ~30x coverage, planted het indels and
known SNPs, quality-correlated errors, soft clips, PCR duplicates —
tools/make_wgs_sam.py) driven through the streamed flagship pipeline
(ingest || markdup summaries || BQSR observe/apply || realign sweeps ||
Parquet part writes; pipelines/streamed.py), the analog of the
reference's `transform -mark_duplicate_reads -recalibrate_base_qualities
-realign_indels` with dbSNP known sites
(adam-cli/.../Transform.scala:101-163 — BASELINE configs 2+3+4 fused).

`vs_baseline` is measured, not assumed: the **same input, same read
count, same streamed code** re-run in a subprocess forced onto the local
CPU backend (the stand-in for the reference's Spark-CPU executors — one
host, all cores), excluding one-time jit compiles on both sides via a
small warmup. Ratio of reads/sec is reported.  The child never loads
the TPU (``JAX_PLATFORMS=cpu``), so it does not contend for the chip
the parent holds.

Secondary lines (one JSON object per line, driver reads line 1):
Smith-Waterman GCUPS (BASELINE metric 2), packed k-mer counting (metric
3 / config 1), per-stage wall split of the chip run, and the CPU
baseline's split.

The chip leg needs a TPU: on any other platform the run fails, and any
phase that fails fails the run.
"""

import hashlib
import json
import os
import subprocess
import sys
import tempfile
import time

_REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, _REPO)
sys.path.insert(0, os.path.join(_REPO, "tools"))

N_READS = 1_000_000
READ_LEN = 100
_TAG = f"adam_tpu_bench_wgs_{N_READS}_{READ_LEN}_v3"


def _bench_cache_dir() -> str:
    """Per-user 0o700 input-cache directory.

    The old cache lived at fixed world-readable /tmp paths validated
    only by file size — any co-tenant could pre-create (or truncate) the
    path and the bench would silently measure their bytes.  The cache is
    now keyed by uid, created 0o700, ownership-checked, and every input
    is content-hash-validated against a manifest written at generation."""
    base = os.environ.get("ADAM_TPU_BENCH_CACHE") or os.path.join(
        tempfile.gettempdir(), f"adam_tpu_bench_u{os.getuid()}"
    )
    os.makedirs(base, mode=0o700, exist_ok=True)
    # ownership check BEFORE chmod: a co-tenant can pre-create the path
    # under sticky /tmp, and chmod-by-non-owner would raise a bare
    # PermissionError instead of this explanation
    st = os.stat(base)
    if st.st_uid != os.getuid():
        raise RuntimeError(
            f"bench cache {base} is owned by uid {st.st_uid}, not "
            f"{os.getuid()} — refusing to trust its contents"
        )
    os.chmod(base, 0o700)
    return base


_CACHE = _bench_cache_dir()
_SYNTH = os.path.join(_CACHE, _TAG + ".sam")
_KNOWN = os.path.join(_CACHE, _TAG + ".known.vcf")


def _sha256(path: str) -> str:
    h = hashlib.sha256()
    with open(path, "rb") as fh:
        for block in iter(lambda: fh.read(1 << 20), b""):
            h.update(block)
    return h.hexdigest()


def _inputs_valid(sam: str, known: str) -> bool:
    """True when both cached inputs match their generation-time hashes."""
    try:
        with open(sam + ".manifest.json") as fh:
            m = json.load(fh)
        return (
            _sha256(sam) == m["sam_sha256"]
            and _sha256(known) == m["known_sha256"]
        )
    except (OSError, ValueError, KeyError):
        return False


def _stamp_inputs(sam: str, known: str) -> None:
    with open(sam + ".manifest.json", "w") as fh:
        json.dump(
            {"sam_sha256": _sha256(sam), "known_sha256": _sha256(known)}, fh
        )


def _ensure_synth() -> None:
    if _inputs_valid(_SYNTH, _KNOWN):
        return
    from make_wgs_sam import make_wgs

    # 4 contigs x 800 kb at 1M x 100 bp ~= 31x coverage
    make_wgs(_SYNTH, N_READS, READ_LEN, known_sites_out=_KNOWN)
    _stamp_inputs(_SYNTH, _KNOWN)


def _known_table():
    from adam_tpu.api.datasets import GenotypeDataset
    from adam_tpu.io import context

    names = context.load_header(_SYNTH).seq_dict.names
    return GenotypeDataset.load(_KNOWN, contig_names=names).snp_table()


def _warmup_compiles(known) -> None:
    """Pay one-time jit compiles outside the timed run (both backends).

    Shape coverage matters more than read count: the streamed pipeline's
    device shapes are the pow2 window grid (window_reads=262144 -> grid
    262144; the 1M run's tail window rounds up to the same) and the
    fixed-CH sweep buckets — so the warm slice must span at least one
    FULL ingest window, or the timed run pays a cold compile per missed
    shape (the round-3 lesson: a 40k-read warmup left ~2 minutes of
    compiles inside the timed region)."""
    from adam_tpu.pipelines.streamed import transform_streamed

    small = _SYNTH + ".warm270k.sam"
    if not os.path.exists(small):
        n = 0
        with open(_SYNTH) as src, open(small + ".tmp", "w") as dst:
            for line in src:
                dst.write(line)
                if not line.startswith("@"):
                    n += 1
                    if n >= 270_000:
                        break
        os.replace(small + ".tmp", small)
    # GEMM sweep tiers compile by (off, rt) only — warm them explicitly
    # so the 1M run cannot hit a shape the 270k slice's consensus-length
    # distribution happened to miss
    from adam_tpu.pipelines import realign as _realign

    _realign.warm_sweep_shapes()
    with tempfile.TemporaryDirectory() as td:
        # same device fan-out as the timed run: the warmup pays the
        # per-device prewarm compiles so the timed windows never do
        transform_streamed(
            small, os.path.join(td, "w.adam"), known_snps=known,
            devices=_DEVICES, partitioner=_PARTITIONER,
        )


#: --devices passthrough (None = all attached / ADAM_TPU_DEVICES).
_DEVICES = None

#: --partitioner passthrough (None = pool / ADAM_TPU_PARTITIONER).
_PARTITIONER = None

#: Zero-filled device leg: the CPU baseline records the SAME keys with
#: empty/zero values so round-over-round artifact diffs stay key-stable.
_NO_DEVICES = {
    "n_devices": 0,
    "devices_used": [],
    "device_kind": None,
    "partitioner": None,
}


def _device_info() -> dict:
    """The chip leg's device context: the device kind, how many chips
    the pool fans out over and which ones."""
    import jax

    from adam_tpu.parallel.device_pool import resolve_device_count

    n = resolve_device_count(_DEVICES)
    # local_devices to match DevicePool: in a multi-process run
    # jax.devices() lists chips this host cannot use
    devs = list(jax.local_devices())[:n]
    return {
        "n_devices": n,
        "devices_used": [int(d.id) for d in devs],
        "device_kind": devs[0].device_kind,
        "partitioner": _PARTITIONER or "pool",
    }


def _denan(o):
    """NaN -> None through nested dicts/lists: the artifact lines must
    stay strict JSON (json.dumps would emit a bare NaN token)."""
    if isinstance(o, float) and o != o:
        return None
    if isinstance(o, dict):
        return {k: _denan(v) for k, v in o.items()}
    if isinstance(o, (list, tuple)):
        return [_denan(v) for v in o]
    return o


def _host_load() -> float:
    try:
        return round(os.getloadavg()[0], 2)
    except OSError:
        return float("nan")


def _run_streamed(known, trials: int = 1) -> dict:
    """Best-of-``trials`` timed runs; every trial records the host 1-min
    load so the spread is attributable (the host's cores are shared).
    The returned dict carries best-trial stages plus the full per-window
    context under ``windows``/``spread`` and the best trial's telemetry
    snapshot under ``telemetry`` (key-stable: device-only counters/
    gauges are zero-filled on the CPU-baseline path instead of omitted,
    so round-over-round artifact diffs never churn on key sets)."""
    from adam_tpu.pipelines.streamed import transform_streamed
    from adam_tpu.utils import telemetry as tele

    best = None
    best_snap = None
    windows = []
    was_recording = tele.TRACE.recording
    for _ in range(max(1, trials)):
        load0 = _host_load()
        # per-trial telemetry window: reset + record so the snapshot
        # attached to the artifact is the BEST trial's, not a blur of
        # all trials
        tele.TRACE.reset()
        tele.TRACE.recording = True
        try:
            with tempfile.TemporaryDirectory() as td:
                stats = transform_streamed(
                    _SYNTH, os.path.join(td, "out.adam"), known_snps=known,
                    devices=_DEVICES, partitioner=_PARTITIONER,
                )
        finally:
            tele.TRACE.recording = was_recording
        snap = tele.key_stable_snapshot()
        windows.append({
            "total_s": round(stats["total_s"], 2),
            "host_load_before": load0,
            "host_load_after": _host_load(),
        })
        if best is None or stats["total_s"] < best["total_s"]:
            best = stats
            best_snap = snap
    totals = sorted(w["total_s"] for w in windows)
    best = dict(best)
    best["telemetry"] = best_snap
    # the analyzer's per-device utilization section (busy/idle/fetch/
    # replay attribution from the best trial's device_spans): every
    # bench artifact carries attribution built in, so the next real-
    # hardware multi-chip round starts from per-chip occupancy data
    # instead of a scalar wall.  Key-stable: the CPU baseline's empty
    # device_spans yields {"wall_s": ..., "devices": {}}.
    from adam_tpu.utils import analyzer

    best["utilization"] = analyzer.utilization_from_snapshot(best_snap)
    best["windows"] = windows
    best["spread"] = {
        "min_s": totals[0],
        "median_s": totals[len(totals) // 2],
        "max_s": totals[-1],
    }
    return best


def _cpu_baseline() -> dict:
    """Same pipeline, same 1M input, local CPU backend -> stats dict."""
    env = dict(os.environ)
    # the child never loads the TPU: the parent holds the chip
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--cpu-child"],
        env=env, capture_output=True, text=True, timeout=3600,
    )
    for line in (proc.stdout or "").splitlines():
        if line.startswith("{"):
            return json.loads(line)
    raise RuntimeError(f"cpu child failed: {proc.stderr[-800:]}")


def _cpu_child() -> None:
    known = _known_table()
    _warmup_compiles(known)
    # two trials plus the recorded loadavg: the host's cores are shared
    stats = _run_streamed(known, trials=2)
    # key-stable device leg: zeros, not omission (see _NO_DEVICES)
    stats["devices"] = dict(_NO_DEVICES)
    print(json.dumps(stats))


def _sw_gcups() -> dict:
    """Smith-Waterman score-fill throughput (BASELINE metric 2): both
    backends, best of 3 trials each, winner labeled."""
    from adam_tpu.ops import smith_waterman as sw

    per_backend = {
        backend: round(sw.benchmark_gcups(backend=backend, trials=3), 2)
        for backend in ("pallas", "scan")
    }
    best = max(per_backend, key=per_backend.get)
    return {"gcups": per_backend[best], "backend": best,
            "per_backend": per_backend}


def _kmers_per_sec() -> float:
    """count_kmers k=21 (BASELINE config 1 analog) on the bench file."""
    import jax
    import jax.numpy as jnp

    from adam_tpu.io import context
    from adam_tpu.ops import kmer

    ds = context.load_alignments(_SYNTH)
    b = ds.batch.to_device()
    args = (jnp.asarray(b.bases), jnp.asarray(b.lengths), jnp.asarray(b.valid))
    out = kmer.device_kmer_histogram(*args, 21)  # compile
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    out = kmer.device_kmer_histogram(*args, 21)
    jax.block_until_ready(out)
    dt = time.perf_counter() - t0
    n_kmers = int(ds.batch.valid.sum()) * (READ_LEN - 21 + 1)
    return n_kmers / dt


def _vs_baseline_windows(stages: dict, cpu_stats: dict) -> dict:
    """Chip-vs-CPU ratios from the recorded windows: ``median`` (median
    chip window over median CPU window — the headline) and ``best``
    (best window over best window)."""
    chip_w = stages.get("windows") or []
    cpu_w = cpu_stats.get("windows") or []
    if not chip_w or not cpu_w:
        return {}

    def _median(ts):
        # true median: even-length lists average the middle pair (with 2
        # CPU windows, index len//2 alone would pick the WORSE one and
        # flatter the chip)
        ts = sorted(ts)
        mid = len(ts) // 2
        return ts[mid] if len(ts) % 2 else (ts[mid - 1] + ts[mid]) / 2

    chip_t = sorted(w["total_s"] for w in chip_w)
    cpu_t = sorted(w["total_s"] for w in cpu_w)
    return {
        "median": round(_median(cpu_t) / _median(chip_t), 2),
        "best": round(cpu_t[0] / chip_t[0], 2),
    }


def main() -> None:
    import jax

    platform = jax.devices()[0].platform
    if platform != "tpu":
        sys.exit(f"bench.py: the chip leg needs a TPU, found {platform!r}")
    _ensure_synth()
    known = _known_table()
    _warmup_compiles(known)
    stages = _run_streamed(known, trials=3)
    rps = stages["n_reads"] / stages["total_s"]
    median_s = stages.get("spread", {}).get("median_s") or stages["total_s"]
    rps_median = stages["n_reads"] / median_s

    cpu_stats = _cpu_baseline()
    cpu_rps = cpu_stats["n_reads"] / cpu_stats["total_s"]
    pairing = _vs_baseline_windows(stages, cpu_stats)
    # headline ratio: median window against median window
    vs = pairing["median"]
    sw_info = _sw_gcups()
    kps = _kmers_per_sec()

    print(
        json.dumps(
            _denan({
                "metric": "transform_e2e_reads_per_sec_per_chip",
                "value": round(rps, 1),
                "median": round(rps_median, 1),
                "unit": (
                    "reads/sec (1M-read WGS-shaped SAM at ~31x: streamed "
                    "ingest+markdup+BQSR(known-sites)+realign+parquet "
                    "parts, one chip; value = best of 3 windows, median = "
                    "median window; CPU baseline = same input/code on host "
                    "cores, 2 windows, vs_baseline = median-vs-median)"
                ),
                "vs_baseline": vs,
            })
        )
    )
    # per-config reads/sec derived from the fused run's stage split
    # (BASELINE configs 2-4; config 1 is the kmers line).  "derived"
    # because each config's wall = its stages + the shared ingest cost;
    # attribution is approximate where stages fuse (observe_s carries
    # the candidate split, realign_s the realigned part's observation —
    # each a few percent of its wall).
    n = stages["n_reads"]

    def _cfg(*keys):
        t = stages.get("ingest_pass_s", 0) + sum(stages.get(k, 0) for k in keys)
        return round(n / t, 1) if t > 0 else None

    configs = {
        "cfg2_markdup_derived_rps": _cfg("resolve_s"),
        # the device backend records dispatch/fetch as disjoint rows
        # next to the host apply share — all of them are BQSR wall
        "cfg3_bqsr_known_sites_derived_rps": _cfg(
            "observe_s", "obs_merge_fetch_s", "solve_s", "apply_split_s",
            "apply_device_dispatch_s", "apply_device_fetch_s",
        ),
        "cfg4_realign_derived_rps": _cfg("realign_s"),
    }
    dev_info = _device_info()
    # per-kernel microbench (utils/kernelbench.py) — isolates the
    # observe/pack/apply/fused_bc inner loops so a fusion can't regress
    # one of them invisibly inside the e2e number; bench-diff flattens
    # rows to kernels.<kernel>.g<g>x<gl>.*
    from adam_tpu.utils.kernelbench import run_kernelbench

    kernels_doc = run_kernelbench(iters=3)
    print(
        json.dumps(
            _denan({
                "metric": "secondary",
                "kernels": kernels_doc,
                "devices": {
                    "chip": dev_info,
                    "cpu_baseline": cpu_stats["devices"],
                },
                "sw": sw_info,
                "kmers_per_sec": round(kps, 1),
                "cpu_baseline_reads_per_sec": round(cpu_rps, 1),
                "vs_baseline_windows": pairing or None,
                **configs,
                "chip_windows": stages.get("windows"),
                "chip_total_spread_s": stages.get("spread"),
                # the CPU baseline runs minutes after the chip windows on
                # shared host cores: its load context must be in the
                # artifact or the ratio can't be read honestly
                "cpu_windows": cpu_stats.get("windows"),
                "chip_stages_s": {
                    k: round(v, 2)
                    for k, v in stages.items()
                    if k.endswith("_s") and isinstance(v, float)
                },
                "cpu_stages_s": {
                    k: round(v, 2)
                    for k, v in cpu_stats.items()
                    if k.endswith("_s") and isinstance(v, float)
                },
                # best-trial telemetry snapshots (spans/counters/gauges;
                # utils/telemetry.py) — per-stage trajectories for
                # future rounds.  Both legs are key-stable: the CPU
                # baseline zero-fills device-only metrics instead of
                # omitting them.
                "telemetry": {
                    "chip": stages.get("telemetry"),
                    "cpu_baseline": cpu_stats.get("telemetry"),
                },
                # per-device busy/idle/fetch/replay attribution of the
                # best trial (utils/analyzer.py) — the multi-chip
                # artifact's occupancy evidence, {} on the CPU leg
                "utilization": {
                    "chip": stages.get("utilization"),
                    "cpu_baseline": cpu_stats.get("utilization"),
                },
            })
        )
    )


def _parse_devices_arg(argv: list) -> None:
    """Consume ``--devices N`` / ``--devices=N`` from argv (sets the
    module-level passthrough).  A missing or non-integer value is a
    usage error, not a crash — and never a silent fall-through to
    all-attached, which would mislabel the artifact."""
    global _DEVICES
    for i, a in enumerate(list(argv)):
        if a == "--devices" or a.startswith("--devices="):
            if a == "--devices":
                val = argv[i + 1] if i + 1 < len(argv) else None
                span = 2
            else:
                val = a.split("=", 1)[1]
                span = 1
            try:
                _DEVICES = int(val)
            except (TypeError, ValueError):
                sys.exit(f"bench.py: --devices needs an integer (got {val!r})")
            del argv[i : i + span]
            return


def _parse_partitioner_arg(argv: list) -> None:
    """Consume ``--partitioner {pool,mesh}`` (the streamed execution
    mode passthrough); invalid values are a usage error so the
    artifact's ``partitioner`` key never mislabels the run."""
    global _PARTITIONER
    for i, a in enumerate(list(argv)):
        if a == "--partitioner" or a.startswith("--partitioner="):
            if a == "--partitioner":
                val = argv[i + 1] if i + 1 < len(argv) else None
                span = 2
            else:
                val = a.split("=", 1)[1]
                span = 1
            if val not in ("pool", "mesh"):
                sys.exit(
                    f"bench.py: --partitioner must be pool or mesh "
                    f"(got {val!r})"
                )
            _PARTITIONER = val
            del argv[i : i + span]
            return


if __name__ == "__main__":
    argv = sys.argv[1:]
    _parse_devices_arg(argv)
    _parse_partitioner_arg(argv)
    if argv and argv[0] == "--cpu-child":
        _cpu_child()
        sys.exit(0)
    main()
