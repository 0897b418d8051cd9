"""A benchmark checkout at a test size, for the CPU tests."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)

#: The flagship configuration cut to a CPU test: 40,000 reads at ~31x.
TINY = dict(n_reads=40_000, n_contigs=2, contig_len=64_000,
            window_reads=16_384)


def make_root(tmp: str, devices=None) -> str:
    """``tmp`` as a checkout: BENCHMARK.json, benchmark/ and the program,
    plus a tiny configuration and its cell ``tiny.discover``."""
    root = os.path.join(tmp, "root")
    shutil.copytree(BENCH, os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__", ".bench"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    os.symlink(os.path.join(REPO, "adam_tpu"), os.path.join(root, "adam_tpu"))
    add_config(root, "tiny", devices=devices)
    add_cell(root, "tiny.discover", "tiny", "discover")
    return root


def add_config(root: str, name: str, devices=None, **over) -> None:
    cfg = json.load(open(os.path.join(
        root, "benchmark", "configs", "wgs-pe100-31x.json")))
    cfg.update(TINY, devices=devices, **over)
    with open(os.path.join(root, "benchmark", "configs", name + ".json"),
              "w") as fh:
        json.dump(cfg, fh)
    edit_bench(root, lambda b: b["configs"].append({
        "name": name, "source": "test", "reduced": [], "why": "test",
        "file": f"benchmark/configs/{name}.json"}))


def add_cell(root: str, name: str, config: str, traffic: str) -> None:
    edit_bench(root, lambda b: b["workloads"].append({
        "name": name, "config": config, "traffic": traffic, "chips": 1,
        "why": "test"}))


def edit_bench(root: str, fn) -> None:
    path = os.path.join(root, "BENCHMARK.json")
    b = json.load(open(path))
    fn(b)
    with open(path, "w") as fh:
        json.dump(b, fh)


def drive(root: str, cell: str, fault: str = "none", trace: int = 0,
          devices: int = 1, program: bool = True) -> subprocess.CompletedProcess:
    """One whole run on the CPU in a process of its own."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               ADAM_TPU_BQSR_BACKEND="device", ADAM_TPU_NO_COMPILE_CACHE="1",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    env["PYTHONPATH"] = root if program else ""
    return subprocess.run(
        [sys.executable, os.path.join(BENCH, "tests", "drive.py"), root, cell,
         fault, "--trace", str(trace)],
        env=env, capture_output=True, text=True, timeout=600, cwd=root)


def result(proc: subprocess.CompletedProcess) -> dict:
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])
