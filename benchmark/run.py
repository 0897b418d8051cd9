#!/usr/bin/env python3
"""One run of one benchmark cell of adam_tpu's streamed ``transform``.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A run generates the cell's input from ``--seed`` (spawned processes,
before JAX loads), checks that JAX sees a TPU with the chips the cell
asks for, runs one warm-up job, then drives the CLI entry
``adam_tpu.cli.main.main(["transform", ...])`` in this process, job after
job, for ``--seconds``; a job started before the deadline runs to its
end.  It then checks every job's output against the plain reference
(``reference.py``) and prints one JSON line last on stdout.

Everything that belongs to one configuration, traffic mix or metric is a
file found by name: ``configs/<config>.json``, ``traffic/<traffic>.json``
and ``metrics/<metric>.json`` (plus ``metrics/<metric>.py`` where a
metric needs code of its own).  With ``--trace 1`` the first job of the
window runs under the JAX profiler and the per-layer metrics are
printed; otherwise the end-to-end ones.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import hashlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import logging  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
WORK = os.path.join(ROOT, ".bench")
TRAFFIC = os.path.join(HERE, "traffic")
sys.path.insert(0, HERE)
sys.path.insert(1, ROOT)  # the program under test: the checkout's adam_tpu

#: Program counters that mean the device path was left (chip_smoke.py).
FAULT_COUNTERS = ("device.evicted", "device.mesh.degraded",
                  "device.resident.evicted")


class BenchError(RuntimeError):
    """A run that cannot produce a result (no chip, a bad cell)."""


def say(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


# ------------------------------------------------------------ the cell


def load_json(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


def load_cell(name: str, root: str = ROOT) -> dict:
    """The cell ``name`` of ``BENCHMARK.json`` with its configuration,
    traffic and metric files."""
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg = load_json(os.path.join(root, configs[cell["config"]]["file"]))
    traffic = load_json(os.path.join(HERE, "traffic",
                                     cell["traffic"] + ".json"))

    def applies(m):
        return name in m.get("workloads", [name])

    metrics = {}
    for kind in ("end_to_end", "per_layer"):
        metrics[kind] = []
        for m in bench[kind]:
            if not applies(m):
                continue
            # the file says how to read the metric; its unit and the
            # rest are BENCHMARK.json's
            spec = load_json(os.path.join(HERE, "metrics", m["name"] + ".json"))
            metrics[kind].append((m["name"], dict(spec, unit=m["unit"])))
    return dict(cell=cell, config=cfg, traffic=traffic, metrics=metrics)


def reader(name: str, spec: dict):
    """``metrics/<name>.py``'s ``read`` when the metric has code of its
    own, else the general reader its file names."""
    path = os.path.join(HERE, "metrics", name + ".py")
    if os.path.exists(path):
        s = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
        mod = importlib.util.module_from_spec(s)
        s.loader.exec_module(mod)
        return mod.read
    return READERS[spec["reader"]]


# ------------------------------------------------------------- readers
#
# A reader takes the run's context and the metric's file and returns a
# number, or None when it finds nothing to read.


def _per_reads(ctx) -> float:
    return sum(j["reads"] for j in ctx["layer_jobs"])


def read_window_rate(ctx, spec):
    jobs = [j for j in ctx["jobs"] if j["ok"]]
    if not jobs:
        return None
    wall = (jobs[-1]["end_ns"] - jobs[0]["start_ns"]) / 1e9
    return sum(j["reads"] for j in jobs) / wall


def read_setup(ctx, spec):
    return ctx["setup_s"]


def read_span_per_mread(ctx, spec):
    spans = [j["snap"]["spans"].get(spec["span"]) for j in ctx["layer_jobs"]]
    if not any(spans):
        return None
    return sum(s["total_s"] for s in spans if s) / (_per_reads(ctx) / 1e6)


def read_counter_per_read(ctx, spec):
    vals = [j["snap"]["counters"].get(spec["counter"])
            for j in ctx["layer_jobs"]]
    if not any(v is not None for v in vals):
        return None
    return sum(v or 0 for v in vals) / _per_reads(ctx)


def read_device_programs(ctx, spec):
    tr = ctx.get("trace")
    if tr is None:
        return None
    s = tr.program_s(spec["patterns"])
    return None if s is None else s / (ctx["traced_job"]["reads"] / 1e6)


def read_device_idle(ctx, spec):
    idle = ctx.get("idle_pct")
    return sum(idle.values()) / len(idle) if idle else None


READERS = {
    "window_rate": read_window_rate,
    "setup": read_setup,
    "span_per_mread": read_span_per_mread,
    "counter_per_read": read_counter_per_read,
    "device_programs": read_device_programs,
    "device_idle": read_device_idle,
}


# ---------------------------------------------------------- the device


def device_gate(chips: int) -> dict:
    """JAX's devices; a run without a TPU, or with too few chips, fails."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"no TPU: JAX's default platform is "
                         f"{devs[0].platform!r}")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    peaks(devs[0].device_kind)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def peaks(kind: str) -> dict:
    """The published peaks of one chip of ``kind`` (``peaks.json``); a
    device missing from the table is an error, never a default."""
    table = load_json(os.path.join(HERE, "peaks.json"))
    if kind not in table or kind == "source":
        raise BenchError(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


class CompileWatch:
    """Counts JAX's lowerings and backend compiles, by phase."""

    LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.phase = "setup"
        self.stats = {}
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **kw):
        if event in (self.LOWER, self.COMPILE):
            key = (self.phase, "lower" if event == self.LOWER else "compile")
            n, t = self.stats.get(key, (0, 0.0))
            self.stats[key] = (n + 1, t + secs)

    def get(self, phase: str, kind: str) -> tuple:
        return self.stats.get((phase, kind), (0, 0.0))


# ---------------------------------------------------------------- jobs


class _ErrorLog(logging.Handler):
    """ERROR records of the package's loggers: the pipeline logs every
    eviction, mesh degrade and host fallback there."""

    def __init__(self):
        super().__init__(level=logging.ERROR)
        self.records = []

    def emit(self, record):
        self.records.append(record.getMessage())


class Jobs:
    """Runs ``transform`` jobs of the cell through the CLI entry."""

    def __init__(self, paths: dict, config: dict, traffic: dict, work: str,
                 variant: str | None = None):
        self.paths = paths
        self.config = config
        self.traffic = traffic
        self.work = work
        # a variant drops the traffic's flags it names: the control
        self.drop = set(traffic["variants"][variant]) if variant else set()
        self.n = 0

    def argv(self, out: str, metrics: str) -> list:
        flags = [f.format(known_sites=self.paths["vcf"])
                 for f in self.traffic["flags"] if f not in self.drop]
        argv = ["transform", self.paths["sam"], out, *flags,
                "-window_reads", str(self.config["window_reads"]),
                "--metrics-json", metrics]
        if self.config.get("devices") is not None:
            argv += ["--devices", str(self.config["devices"])]
        if self.config.get("partitioner") is not None:
            argv += ["--partitioner", self.config["partitioner"]]
        return argv

    def run(self, n_reads: int) -> dict:
        """One job; the record says how it went and what it wrote."""
        from adam_tpu.cli.main import main
        from adam_tpu.pipelines import bqsr
        from adam_tpu.utils import telemetry as tele

        k = self.n
        self.n += 1
        out = os.path.join(self.work, f"out.{k}")
        mpath = os.path.join(self.work, f"metrics.{k}.json")
        tele.TRACE.reset()
        h = _ErrorLog()
        lg = logging.getLogger("adam_tpu")
        lg.addHandler(h)
        problems = []
        start = time.monotonic_ns()
        try:
            rc = main(self.argv(out, mpath))
        except Exception:  # a failed job is counted, the run goes on
            rc = None
            problems.append("raised: " + traceback.format_exc(limit=4))
        finally:
            end = time.monotonic_ns()
            lg.removeHandler(h)
        job = dict(k=k, out=out, start_ns=start, end_ns=end, reads=n_reads,
                   rc=rc, snap={"spans": {}, "counters": {}, "gauges": {}})
        job["events"] = [(e["name"], e["ts_ns"], e["ts_ns"] + e["dur_ns"])
                         for e in tele.TRACE.events()
                         if e["name"].startswith("streamed.")]
        if rc is not None and rc != 0:
            problems.append(f"exit code {rc}")
        if h.records:
            problems.append(f"logged errors: {h.records[:3]}")
        if os.path.exists(mpath):
            job["snap"] = load_json(mpath)
        c = job["snap"]["counters"]
        if rc == 0:
            if bqsr.bqsr_backend() != "device":
                problems.append(f"BQSR backend {bqsr.bqsr_backend()!r}")
            for name in FAULT_COUNTERS:
                if c.get(name):
                    problems.append(f"{name} = {c[name]}")
            sort = job["snap"]["gauges"].get("streamed.resolve.device_sort", {})
            if sort.get("last") != 1:
                problems.append("duplicate-resolve sort ran on the host")
            if c.get("reads.ingested") != n_reads:
                problems.append(f"ingested {c.get('reads.ingested')} of "
                                f"{n_reads} reads")
            if not c.get("device.h2d.bytes"):
                problems.append("no bytes went to the device")
        job["problems"] = problems
        job["ok"] = not problems
        return job


def parts_digest(out: str) -> str:
    h = hashlib.sha256()
    for f in sorted(os.listdir(out)) if os.path.isdir(out) else []:
        if f.startswith("part-"):
            h.update(f.encode())
            with open(os.path.join(out, f), "rb") as fh:
                for block in iter(lambda: fh.read(1 << 24), b""):
                    h.update(block)
    return h.hexdigest()


# --------------------------------------------------------------- check


def check_outputs(jobs: list, paths: dict, traffic: dict) -> dict:
    """Compare the output of every job with the reference: each distinct
    output (by the digest of its parts) is compared once."""
    import numpy as np

    import reference as ref

    t = time.perf_counter()
    inp = ref.load_input(paths["sam"])
    truth = dict(np.load(paths["truth"]))
    known = ref.load_known_sites(paths["vcf"], inp["contigs"])
    times = {"input": time.perf_counter() - t, "output": 0.0, "compare": 0.0}
    worst = {}
    seen = {}
    for j in jobs:
        if j["rc"] != 0 or not os.path.isdir(j["out"]):
            continue
        d = parts_digest(j["out"])
        if d not in seen:
            t = time.perf_counter()
            out = ref.load_output(j["out"])
            times["output"] += time.perf_counter() - t
            t = time.perf_counter()
            seen[d] = ref.compare(inp, out, truth, known, traffic["expect"])
            times["compare"] += time.perf_counter() - t
        for k, v in seen[d].items():
            worst[k] = max(worst.get(k, v), v)
    say(f"check: {len(jobs)} jobs, {len(seen)} distinct outputs; seconds "
        f"{json.dumps(times)}")
    return worst


# ----------------------------------------------------------------- run


def generate(cfg: dict, traffic: dict, seed: int, work: str,
             procs: int | None = None) -> dict:
    # on sys.path, so that the generator's spawned workers import it too
    if TRAFFIC not in sys.path:
        sys.path.insert(0, TRAFFIC)
    gen = importlib.import_module(traffic["generator"])
    if procs is None:
        small = cfg["n_reads"] < 200_000
        procs = 1 if small else max(1, min(3 * cfg["n_contigs"],
                                            (os.cpu_count() or 2) - 1))
    return gen.generate(work, seed, cfg, procs=procs)


def run(args, gate=device_gate, root: str = ROOT, work: str = WORK,
        variant: str | None = None) -> dict:
    """One run; returns the result line's object.  ``variant`` names one
    of the traffic file's ``variants``, run in place of the program (the
    control, driven by the tests)."""
    cell = load_cell(args.workload, root)
    cfg, traffic = cell["config"], cell["traffic"]
    if os.path.isdir(work):
        shutil.rmtree(work)
    os.makedirs(work)

    # the chip first, so a run without one fails at once; the spawned
    # generator workers import no JAX
    device = gate(cell["cell"]["chips"])
    watch = CompileWatch()
    import jax

    import adam_tpu  # noqa: F401  (places the compile cache)

    t = time.perf_counter()
    paths = generate(cfg, traffic, args.seed, work)
    gen_s = time.perf_counter() - t
    n_reads = cfg["n_reads"]  # every job reads the whole input

    say(f"device {device}; compile cache "
        f"{jax.config.jax_compilation_cache_dir}")
    jobs = Jobs(paths, cfg, traffic, work, variant)
    warm = jobs.run(n_reads)
    say(f"warm-up job: {(warm['end_ns'] - warm['start_ns']) / 1e9} s, "
        f"problems {warm['problems']}")
    setup_s = time.perf_counter() - T_START
    nc, tc = watch.get("setup", "compile")
    nl, _ = watch.get("setup", "lower")
    prog = {k: v for k, v in warm["snap"]["counters"].items()
            if k.startswith("device.compile.")}
    say(f"setup_s {setup_s}: input generation {gen_s} s, backend compiles "
        f"{nc} ({tc} s), lowerings {nl}, warm-up job "
        f"{(warm['end_ns'] - warm['start_ns']) / 1e9} s, program's "
        f"compile counters {json.dumps(prog)}")

    watch.phase = "window"
    trace_dir = os.path.join(work, "xprof")
    window = []
    deadline = time.monotonic_ns() + int(args.seconds * 1e9)
    while time.monotonic_ns() < deadline:
        traced = args.trace and not window
        if traced:
            import jax.profiler as jp

            opts = jp.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jp.start_trace(trace_dir, profiler_options=opts)
            annot_ns = time.monotonic_ns()
            with jp.TraceAnnotation("bench.job"):
                job = jobs.run(n_reads)
            jp.stop_trace()
            job["annot_ns"] = annot_ns
            job["traced"] = True
        else:
            job = jobs.run(n_reads)
        window.append(job)
        say(f"job {job['k']}: {(job['end_ns'] - job['start_ns']) / 1e9} s"
            + (f", problems {job['problems']}" if job["problems"] else ""))
    watch.phase = "check"
    nl, _ = watch.get("window", "lower")
    nc, tc = watch.get("window", "compile")
    in_window = sum(j["snap"]["counters"].get("device.compile.in_window", 0)
                    for j in window)
    say(f"window: {len(window)} jobs, lowerings {nl}, backend compiles "
        f"{nc} ({tc} s), device.compile.in_window {in_window}")

    used = [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in jax.devices()]
    device["memory_peak_bytes"] = max((p for p in used if p is not None),
                                      default=None)

    ctx = dict(jobs=window, setup_s=setup_s)
    ctx["layer_jobs"] = [j for j in window if not j.get("traced")] or window
    result_extra = {}
    if args.trace:
        import devtrace

        tj = window[0]
        ctx["traced_job"] = tj
        path = devtrace.find_xplane(trace_dir)
        tr = devtrace.Trace(path) if path else None
        if tr is not None and tr.job is not None:
            ctx["trace"] = tr
            chips = [f"/device:TPU:{i}" for i in range(cell["cell"]["chips"])]
            busy = tr.busy_s()
            idle = tr.idle_pct()
            if idle:  # a chip with no op at all in the job was idle
                ctx["idle_pct"] = {p: idle.get(p, 100.0) for p in chips}
            device["busy_s"] = sum(busy.get(p, 0.0) for p in chips) / len(chips)
            device["window_s"] = tr.window_s()
            say(f"trace: planes {sorted(tr.planes)}; per-chip idle % "
                f"{ctx.get('idle_pct')}")
            offset = tr.job[0] - tj["annot_ns"]
            result_extra["breakdown"] = {
                "device_ops": tr.top_programs(10),
                "idle_gaps": tr.idle_gaps(tj["events"], offset, 10),
            }
        else:
            say(f"trace: no profile read from {trace_dir}")

    kind = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for name, spec in cell["metrics"][kind]:
        v = reader(name, spec)(ctx, spec)
        if v is not None:
            metrics[name] = {"value": v, "unit": spec["unit"]}
    stages = {}
    for j in ctx["layer_jobs"]:
        for k, v in j["snap"]["spans"].items():
            if k.startswith("streamed."):
                stages[k] = stages.get(k, 0.0) + v["total_s"]
    say(f"stage walls summed over {len(ctx['layer_jobs'])} jobs: "
        f"{json.dumps(stages)}")

    t = time.perf_counter()
    numbers = check_outputs(window, paths, traffic)
    say(f"reference check took {time.perf_counter() - t} s")
    failed = sum(not j["ok"] for j in window)
    checks = {}
    correct = failed == 0 and bool(window)
    for k, limit in traffic["limits"].items():
        v = numbers.get(k)
        ok = v is not None and limit is not None and v <= limit
        correct &= ok
        checks[k] = {"value": v, "limit": limit}
    shutil.rmtree(work, ignore_errors=True)
    for k, c in checks.items():
        say(f"check {k}: {c['value']} (limit {c['limit']})")
    out = {"correct": bool(correct), "attempted": len(window),
           "failed": failed, "metrics": metrics, "device": device}
    out.update(result_extra)
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        res = run(args)
    except BenchError as e:
        say(f"FAIL: {e}")
        return 2
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
