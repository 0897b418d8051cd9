"""Drive a whole benchmark run on the CPU at a test size, with the timed
path broken underneath, and print its result line.

    python benchmark/tests/drive.py <root> <cell> <fault> [seed]

``root`` holds ``BENCHMARK.json`` and ``benchmark/``; the harness's look
for a chip is skipped.  ``fault`` is one of :data:`FAULTS`, each of which
breaks one thing the cell's output is held to where it is produced, or
a variant of the cell's traffic file (the control).  Run in a
process of its own (tests/test_faults.py), because JAX's device count
and the patches are per process.
"""

from __future__ import annotations

import argparse
import glob
import importlib.util
import json
import os
import sys


def _rewrite_parts(out: str, fn) -> None:
    import pyarrow.parquet as pq

    for p in sorted(glob.glob(os.path.join(out, "part-*"))):
        t = pq.read_table(p)
        pq.write_table(fn(t), p)


def _half(t):
    return t.slice(0, t.num_rows // 2)


def _alter_one(t):
    """One residue's recalibrated quality raised by one."""
    import pyarrow as pa

    q = t["qual"].to_pylist()
    i = next(i for i, s in enumerate(q) if s)
    q[i] = chr(ord(q[i][0]) + 1) + q[i][1:]
    return t.set_column(t.column_names.index("qual"), "qual",
                        pa.array(q, t["qual"].type))


def _unchanged(t):
    """The records as they came in: input qualities, no duplicate flag."""
    import pyarrow as pa
    import pyarrow.compute as pc

    t = t.set_column(t.column_names.index("qual"), "qual",
                     t["origQual"].cast(t["qual"].type))
    flags = pc.bit_wise_and(t["flags"], pa.scalar(~0x400, pa.int32()))
    return t.set_column(t.column_names.index("flags"), "flags", flags)


def _half_realign(ds, *a, real, **kw):
    """Realignment skipped on every other 10 kb of each contig: the
    candidates there are returned as they came in."""
    import numpy as np

    from adam_tpu.api.datasets import AlignmentDataset

    skip = (np.asarray(ds.batch.to_numpy().start) // 10_000) % 2 == 1
    done = real(ds.take_rows(np.flatnonzero(~skip)), *a, **kw)
    return AlignmentDataset.concat([done, ds.take_rows(np.flatnonzero(skip))])


OUTPUT_FAULTS = {"half_batch": _half, "altered_answer": _alter_one,
                 "unchanged": _unchanged}
#: Faults planted in the program's code; the traffic file's `variants`
#: (flags dropped: the control) are run by the harness itself.
FAULTS = ("none", "no_exchange", "half_realign", *OUTPUT_FAULTS)


def patch(fault: str):
    """Plant ``fault`` in the program; returns the call that undoes it."""
    import adam_tpu.cli.main as cli
    from adam_tpu.pipelines import bqsr, realign

    if fault in OUTPUT_FAULTS:
        mod, name = cli, "main"
        real = cli.main

        def new(argv):
            rc = real(argv)
            _rewrite_parts(argv[2], OUTPUT_FAULTS[fault])
            return rc
    elif fault == "no_exchange":
        # each device's observations stay on it: only the first part of
        # the histogram merge is kept
        mod, name = bqsr, "merge_observations"
        real = bqsr.merge_observations

        def new(parts, *a, **kw):
            if kw.get("window_ids") is not None:
                kw["window_ids"] = kw["window_ids"][:1]
            return real(parts[:1], *a, **kw)
    elif fault == "half_realign":
        mod, name = realign, "realign_indels"
        real = realign.realign_indels

        def new(ds, *a, **kw):
            return _half_realign(ds, *a, real=real, **kw)
    else:
        return lambda: None
    setattr(mod, name, new)
    return lambda: setattr(mod, name, real)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("root")
    ap.add_argument("cell")
    ap.add_argument("fault", help="one of FAULTS, or a variant of the "
                    "cell's traffic file")
    ap.add_argument("seed", type=int, nargs="?", default=2**31 + 7)
    ap.add_argument("--trace", type=int, default=0)
    a = ap.parse_args()
    spec = importlib.util.spec_from_file_location(
        "bench_run", os.path.join(a.root, "benchmark", "run.py"))
    run_mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run_mod)
    variants = run_mod.load_cell(a.cell, a.root)["traffic"]["variants"]
    if a.fault in variants:
        variant = a.fault
    else:
        variant = None
        if a.fault not in FAULTS:
            ap.error(f"no fault or variant {a.fault!r}")
        patch(a.fault)
    args = argparse.Namespace(workload=a.cell, seed=a.seed, seconds=0.5,
                              trace=a.trace)
    import jax

    res = run_mod.run(args, gate=lambda chips: {
        "platform": "cpu", "kind": "cpu", "count": len(jax.devices())},
        root=a.root, work=os.path.join(a.root, ".bench"), variant=variant)
    print(json.dumps(res))
    return 0


if __name__ == "__main__":
    sys.exit(main())
