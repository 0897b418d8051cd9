"""Two-process jax.distributed harness (run by test_parallel.py).

Each process contributes one CPU device; the genome mesh spans both, so
the shard_map collectives (all_gather / all_to_all / psum) in
adam_tpu.parallel.dist really cross a process boundary over the gRPC
DCN transport — the single-host simulation of SURVEY §2.6's multi-host
requirement (the reference's analog: Spark executors shuffling over TCP).

Usage: python multihost_harness.py <coordinator> <num_procs> <proc_id>
           [transform <shard_dir> <out_dir>]
Prints "HARNESS OK <checksum>" on success from every process.

The ``transform`` mode runs the COMPOSED flagship transform
(markdup -> realign -> BQSR, the reference's Transform composition)
across the processes over a shared raw shard store: each process owns alternating genome-bin shards,
duplicate-marking summaries and realignment candidates exchange through
spill files (the disk-shuffle role Spark's block manager plays), and
the BQSR observation histograms merge with a REAL cross-process device
``psum`` over the 2-device gRPC mesh.  test_parallel.py asserts the
concatenated output equals the monolithic single-process transform.
"""

import os
import sys

# one CPU device per process
os.environ["JAX_PLATFORMS"] = "cpu"
flags = [
    f
    for f in os.environ.get("XLA_FLAGS", "").split()
    if "xla_force_host_platform_device_count" not in f
]
flags.append("--xla_force_host_platform_device_count=1")
os.environ["XLA_FLAGS"] = " ".join(flags)

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    coordinator, n_procs, pid = sys.argv[1], int(sys.argv[2]), int(sys.argv[3])

    import jax

    from adam_tpu.parallel.mesh import initialize_distributed

    initialize_distributed(coordinator, n_procs, pid)

    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from adam_tpu.parallel import dist
    from adam_tpu.parallel.mesh import SHARD_AXIS, genome_mesh

    devices = jax.devices()
    assert len(devices) == n_procs, f"expected {n_procs} devices, got {devices}"
    mesh = genome_mesh(devices)
    sharding = NamedSharding(mesh, P(SHARD_AXIS))

    # ---- distributed sort across processes ----
    m = 64
    rng = np.random.default_rng(1234)
    global_keys = rng.integers(0, 2**40, n_procs * m, dtype=np.int64)
    local = global_keys[pid * m : (pid + 1) * m]
    keys = jax.make_array_from_process_local_data(sharding, local)
    out = dist.distributed_sort_keys(keys, mesh)

    from jax.experimental import multihost_utils

    gathered = np.asarray(
        multihost_utils.process_allgather(out, tiled=True)
    ).ravel()
    real = gathered[gathered != np.iinfo(np.int64).max]
    expected = np.sort(global_keys)
    assert len(real) == len(expected), (len(real), len(expected))
    assert (real == expected).all(), "distributed sort mismatch"

    # ---- psum-combined flagstat-style reduction across processes ----
    import jax.numpy as jnp
    from functools import partial
    from jax import shard_map

    @jax.jit
    @partial(
        shard_map, mesh=mesh, in_specs=(P(SHARD_AXIS),), out_specs=P(),
        check_vma=False,
    )
    def total(x):
        return jax.lax.psum(x.sum(), SHARD_AXIS)

    t = total(keys)
    assert int(t) == int(global_keys.sum()), "psum mismatch"

    print(f"HARNESS OK {int(expected[0]) % 100000}", flush=True)


def transform_main(coordinator: str, n_procs: int, pid: int,
                   shard_dir: str, out_dir: str) -> None:
    """Composed 2-process transform over a shared raw shard store."""
    import pickle

    import jax

    from adam_tpu.parallel.mesh import initialize_distributed

    initialize_distributed(coordinator, n_procs, pid)

    import glob as globmod
    from functools import partial

    import jax.numpy as jnp
    import numpy as np
    from jax import shard_map
    from jax.experimental import multihost_utils
    from jax.sharding import NamedSharding, PartitionSpec as P

    from adam_tpu.api.datasets import AlignmentDataset
    from adam_tpu.parallel import spill
    from adam_tpu.parallel.mesh import SHARD_AXIS, genome_mesh
    from adam_tpu.pipelines import bqsr as bqsr_mod
    from adam_tpu.pipelines import markdup as md_mod
    from adam_tpu.pipelines import realign as realign_mod
    from adam_tpu.pipelines.streamed import _write_part

    # record per-host spans/counters so the merge-barrier telemetry
    # gather below has real per-host data to show skew over
    from adam_tpu.utils import telemetry as _telemetry

    _telemetry.TRACE.recording = True

    mesh = genome_mesh(jax.devices())
    # only real shards: the candidate spills below also live here
    shard_paths = sorted(
        globmod.glob(os.path.join(shard_dir, "shard-*.arrows"))
    )
    mine = [si for si in range(len(shard_paths)) if si % n_procs == pid]

    def load(si):
        b, s, h = spill.read_raw_shard(shard_paths[si])
        return AlignmentDataset(b, s, h)

    def barrier(tag):
        multihost_utils.sync_global_devices(tag)

    # ---- pass A: per-process summaries + indel events ------------------
    summaries = {}
    events_local = []
    header = None
    counts = {}
    for si in mine:
        ds = load(si)
        header = ds.header
        counts[si] = ds.batch.n_rows
        summaries[si] = md_mod.row_summary(ds)
        events_local.extend(
            realign_mod.extract_indel_events(ds.batch.to_numpy())
        )

    # exchange summaries + events (and the header, so a process that
    # owns zero shards can still participate in the collectives)
    # through spill files (disk shuffle)
    with open(os.path.join(shard_dir, f"sum-{pid}.pkl"), "wb") as fh:
        pickle.dump((summaries, events_local, counts, header), fh)
    barrier("summaries")
    all_summaries = {}
    all_events = []
    all_counts = {}
    for p in range(n_procs):
        with open(os.path.join(shard_dir, f"sum-{p}.pkl"), "rb") as fh:
            s, e, c, h = pickle.load(fh)
        all_summaries.update(s)
        all_events.extend(e)
        all_counts.update(c)
        if header is None:
            header = h
    assert header is not None, "no process owned any shard"

    # ---- barrier: global duplicate resolve + target merge (identical
    # decisions in both processes — shard order fixes the splice order)
    order = sorted(all_summaries)
    dup = md_mod.resolve_duplicates(
        md_mod.concat_summaries([all_summaries[si] for si in order])
    )
    dup_slices = {}
    off = 0
    for si in order:
        dup_slices[si] = dup[off: off + all_counts[si]]
        off += all_counts[si]
    targets = realign_mod.merge_events(all_events, header.seq_dict.names)

    def with_dup(ds, si):
        b = ds.batch.to_numpy()
        return ds.with_batch(b.replace(flags=md_mod.apply_duplicate_flags(
            np.asarray(b.flags), dup_slices[si]
        )))

    # ---- pass B: candidate split (pre-BQSR, the reference's markdup ->
    # realign -> BQSR order) + local observation of shard remainders ----
    parts = []
    cand_local = []
    for si in mine:
        ds = with_dup(load(si), si)
        n_valid = ds.batch.n_rows
        if targets:
            b = ds.batch.to_numpy()
            keep = realign_mod.candidate_mask(
                b, targets, header.seq_dict.names
            )
            if keep.any():
                cand_local.append(ds.take_rows(np.flatnonzero(keep)))
                ds = realign_mod.mask_out_candidates(
                    ds, targets, header.seq_dict.names, mask=keep
                )
                n_valid = int(np.asarray(ds.batch.valid).sum())
        if n_valid:
            total, mism, _rg, g = bqsr_mod._observe_device(ds, None)
            parts.append((np.asarray(total), np.asarray(mism), g))

    # exchange candidates; pid 0 realigns them all (boundary-correct)
    # and observes the realigned part so its POST-realignment
    # observations enter the global table
    cpath = os.path.join(shard_dir, f"cand-{pid}.arrows")
    if cand_local:
        cand = AlignmentDataset.concat(cand_local)
        w = spill.RawShardWriter(cpath)
        w.append(cand.batch, cand.sidecar, cand.header)
        w.close()
    barrier("candidates")
    realigned = None
    if pid == 0:
        cands = []
        for p2 in range(n_procs):
            cp = os.path.join(shard_dir, f"cand-{p2}.arrows")
            if os.path.exists(cp):
                b2, s2, h2 = spill.read_raw_shard(cp)
                cands.append(AlignmentDataset(b2, s2, h2))
        if cands:
            realigned = realign_mod.realign_indels(
                AlignmentDataset.concat(cands)
            )
            if realigned.batch.n_rows:
                total, mism, _rg, g = bqsr_mod._observe_device(
                    realigned, None
                )
                parts.append((np.asarray(total), np.asarray(mism), g))

    if parts:
        lt, lm, lgl = bqsr_mod.merge_observations(parts)
    else:
        lt = lm = None
        lgl = 0
    # common table width across processes, then a REAL psum over DCN
    gls = multihost_utils.process_allgather(jnp.int32(lgl))
    gl = int(np.max(np.asarray(gls)))
    n_rg = len(header.read_groups) + 1
    shape = (n_rg, bqsr_mod.N_QUAL, 2 * gl + 1, bqsr_mod.N_DINUC)
    pt = np.zeros(shape, np.int64)
    pm = np.zeros(shape, np.int64)
    if lt is not None:
        o = gl - lgl
        pt[:, :, o: o + 2 * lgl + 1, :] = lt
        pm[:, :, o: o + 2 * lgl + 1, :] = lm

    sharding = NamedSharding(mesh, P(SHARD_AXIS))

    @jax.jit
    @partial(shard_map, mesh=mesh, in_specs=(P(SHARD_AXIS),),
             out_specs=P(), check_vma=False)
    def psum_hist(x):
        return jax.lax.psum(x, SHARD_AXIS)

    def psum_table(local):
        # exact for counts < 2^53 (f64; i64 vector ops are emulated)
        arr = jax.make_array_from_process_local_data(
            sharding, local.reshape(1, -1).astype(np.float64)
        )
        out = np.asarray(psum_hist(arr))  # replicated: fully addressable
        return out.reshape(shape).astype(np.int64)

    total = psum_table(pt)
    mism = psum_table(pm)
    table = bqsr_mod.solve_recalibration_table(total, mism)

    # ---- telemetry gather at the merge barrier: every process ships
    # its snapshot over the same DCN transport the psum rode, and pid 0
    # writes the per-host skew report next to the output parts (the
    # leading underscore keeps Parquet discovery off it) --------------
    from adam_tpu.parallel import dist as dist_mod
    from adam_tpu.utils import telemetry

    host_snaps = dist_mod.gather_host_telemetry()
    assert len(host_snaps) == n_procs
    if pid == 0:
        with open(os.path.join(out_dir, "_telemetry.json"), "w") as fh:
            import json

            json.dump(telemetry.merge_snapshots(host_snaps), fh, default=str)

    # ---- pass C: apply the global table to shard remainders (re-split
    # under the same rule) and, on pid 0, to the realigned part ----------
    for si in mine:
        ds = with_dup(load(si), si)
        if targets:
            ds = realign_mod.mask_out_candidates(
                ds, targets, header.seq_dict.names
            )
        ds = bqsr_mod.apply_recalibration(ds, table, gl)
        if int(np.asarray(ds.batch.valid).sum()):
            _write_part(out_dir, si, ds, "zstd")
    if realigned is not None:
        realigned = bqsr_mod.apply_recalibration(realigned, table, gl)
        _write_part(out_dir, len(shard_paths), realigned, "zstd")
    barrier("done")
    import resource

    ru = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
    # ru_maxrss is kilobytes on Linux, bytes on Darwin
    rss_gb = ru / (1e9 if sys.platform == "darwin" else 1e6)
    print(
        f"HARNESS OK {int(total.sum()) % 100000} rss_gb={rss_gb:.2f}",
        flush=True,
    )


def run_composition(
    n_procs: int, shard_dir: str, out_dir: str, timeout: int = 900
) -> list[tuple[str, float]]:
    """Spawn ``n_procs`` OS processes running the composed transform over
    an existing raw shard store -> per-process (output, peak_rss_gb)
    pairs.  Shared by test_parallel.py and the driver's dryrun tail.

    Pipes drain on one thread per child: the children synchronize at
    barriers, so sequential communicate() would deadlock if any
    non-first child filled its pipe before everyone reached "done"."""
    import re
    import socket
    import subprocess
    import threading

    with socket.socket() as s:
        s.bind(("localhost", 0))
        port = s.getsockname()[1]
    coord = f"localhost:{port}"
    here = os.path.abspath(__file__)
    os.makedirs(out_dir, exist_ok=True)
    procs = [
        subprocess.Popen(
            [sys.executable, here, coord, str(n_procs), str(pid),
             "transform", shard_dir, out_dir],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            env=dict(os.environ),
        )
        for pid in range(n_procs)
    ]
    outs: list = [None] * n_procs
    errs: list = [None] * n_procs

    def drain(i):
        try:
            outs[i], _ = procs[i].communicate(timeout=timeout)
        except BaseException as e:  # timeout etc: recorded, proc killed
            errs[i] = e
    threads = [
        threading.Thread(target=drain, args=(i,)) for i in range(n_procs)
    ]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    finally:
        for p in procs:
            p.kill()
    results = []
    for pid, (p, out) in enumerate(zip(procs, outs)):
        if errs[pid] is not None or p.returncode != 0 or not out \
                or "HARNESS OK" not in out:
            raise RuntimeError(
                f"composition proc {pid}/{n_procs} failed "
                f"(rc={p.returncode}, err={errs[pid]!r}):"
                f"\n{(out or '')[-3000:]}"
            )
        m = re.search(r"rss_gb=([0-9.]+)", out)
        if not m:
            raise RuntimeError(
                f"composition proc {pid} reported no RSS:\n{out[-500:]}"
            )
        results.append((out, float(m.group(1))))
    return results


if __name__ == "__main__":
    if len(sys.argv) > 4 and sys.argv[4] == "transform":
        transform_main(sys.argv[1], int(sys.argv[2]), int(sys.argv[3]),
                       sys.argv[5], sys.argv[6])
    else:
        main()
