"""Readings that the limits of ``correct`` are set from, on the chip at
a cell's own size, in one process:

    python3 benchmark/tests/chip_readings.py --workload <cell> \\
        --seeds 201 202 ... [--controls 3]

For each seed: the cell's input, one job of the program as the cell runs
it, and the reference's numbers for its output (the lower readings).
For the first ``--controls`` seeds also the control and the faults (the
upper readings): the traffic file's variants, such as the program with
known-site masking switched off (a guarantee of the configuration
broken), and faults planted in the program (``drive.FAULTS``), such as
realignment skipped on half the genome.  One JSON line per job on stdout.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(1, HERE)

import drive  # noqa: E402
import run  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--variants", nargs="+", default=None,
                    help="what runs beside the program on the first "
                         "--controls seeds: variants of the traffic file "
                         "and faults of drive.FAULTS (default: the "
                         "traffic file's variants)")
    a = ap.parse_args()
    cell = run.load_cell(a.workload)
    cfg, traffic = cell["config"], cell["traffic"]
    run.device_gate(cell["cell"]["chips"])
    import adam_tpu  # noqa: F401

    for i, seed in enumerate(a.seeds):
        work = os.path.join(run.WORK, "readings")
        shutil.rmtree(work, ignore_errors=True)
        os.makedirs(work)
        paths = run.generate(cfg, traffic, seed, work)
        extra = a.variants or list(traffic["variants"])
        for v in ["program"] + (extra if i < a.controls else []):
            variant = v if v in traffic["variants"] else None
            undo = drive.patch(v) if variant is None else (lambda: None)
            try:
                jobs = run.Jobs(paths, cfg, traffic, work, variant)
                job = jobs.run(cfg["n_reads"])
            finally:
                undo()
            t = time.perf_counter()
            nums = run.check_outputs([job], paths, traffic)
            print(json.dumps({
                "seed": seed, "variant": v,
                "job_s": (job["end_ns"] - job["start_ns"]) / 1e9,
                "check_s": time.perf_counter() - t,
                "problems": job["problems"], **nums}), flush=True)
            shutil.rmtree(job["out"], ignore_errors=True)
    shutil.rmtree(run.WORK, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
