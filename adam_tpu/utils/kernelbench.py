"""Per-kernel microbench harness (docs/PERF.md "Megakernel tier").

The end-to-end bench (``bench.py``) measures the pipeline; this module
times each registered device kernel in isolation per grid bucket, so a
fusion cannot regress one inner loop invisibly inside the e2e number.
The shape is the classic simple-timeit: one untimed warmup dispatch
(compile), then ``iters`` timed dispatches each blocked to completion.

The result is a stable-schema JSON document (:data:`SCHEMA`):

``{"schema": ..., "jax_backend": "cpu"|"tpu", "rows": [
    {"kernel", "g", "gl", "iters", "mean_s", "best_s"}, ...]}``

``jax_backend`` names where the rows ran: only ``tpu`` rows are device
times.  ``bench.py`` embeds the document under the secondary line's
``"kernels"`` key, which ``scripts/bench-diff`` flattens to
``kernels.<kernel>.g<g>x<gl>.*``; ``scripts/kernel-bench`` is the CLI.
"""

from __future__ import annotations

import time

import numpy as np

SCHEMA = "adam_tpu.kernelbench/1"

#: Default grid buckets (rows, lanes), pow2-quantized like the
#: streamed windows' grids.
DEFAULT_GRIDS = ((256, 128),)

KERNELS = ("observe", "pack", "apply", "fused_bc")


def _synth(g: int, gl: int, n_rg: int, seed: int = 7) -> dict:
    """Deterministic synthetic window at grid (g, gl) — realistic
    payload densities (the scatter/gather costs are shape-dominated,
    but all-zero masks would let an optimizer elide the interesting
    work)."""
    from adam_tpu.ops.colpack import pack_mask_bits

    rng = np.random.default_rng(seed)
    residue_ok = rng.random((g, gl)) < 0.95
    is_mm = rng.random((g, gl)) < 0.01
    return {
        "bases": rng.integers(0, 4, (g, gl), dtype=np.uint8),
        "quals": rng.integers(2, 40, (g, gl), dtype=np.uint8),
        "lengths": np.full((g,), gl, np.int32),
        "flags": np.zeros((g,), np.int32),
        "read_group_idx": rng.integers(
            0, max(n_rg - 1, 1), (g,), dtype=np.int32
        ),
        "res_bits": pack_mask_bits(residue_ok),
        "mm_bits": pack_mask_bits(is_mm),
        "read_ok": np.ones((g,), bool),
        "has_qual": np.ones((g,), bool),
        "valid": np.ones((g,), bool),
        "table": rng.integers(
            2, 40, (n_rg, 94, 2 * gl + 1, 17), dtype=np.uint8
        ),
    }


def _build(kernel: str, g: int, gl: int, n_rg: int):
    """-> zero-arg dispatch thunk for one (kernel, grid) pair, args
    pre-placed so the timed region is dispatch+execute only."""
    import jax

    from adam_tpu.pipelines.bqsr import jit_variant

    a = _synth(g, gl, n_rg)
    put = jax.device_put
    row5 = tuple(put(a[k]) for k in (
        "bases", "quals", "lengths", "flags", "read_group_idx"
    ))
    if kernel == "observe":
        args = row5 + (
            put(a["res_bits"]), put(a["mm_bits"]), put(a["read_ok"]),
        )
        return lambda: jit_variant("observe_packed", False)(
            *args, n_rg, gl
        )
    if kernel == "pack":
        from adam_tpu.ops.colpack import pack_rows_kernel

        mat = put(a["quals"])
        lens = put(a["lengths"].astype(np.int64))
        return lambda: pack_rows_kernel(mat, lens, g * gl)
    if kernel == "apply":
        args = row5 + (
            put(a["has_qual"]), put(a["valid"]), put(a["table"]),
        )
        return lambda: jit_variant("apply_pack2", False)(
            *args, gl, g * gl
        )
    if kernel == "fused_bc":
        args = row5 + (
            put(a["res_bits"]), put(a["mm_bits"]), put(a["read_ok"]),
            put(a["has_qual"]), put(a["valid"]), put(a["table"]),
        )
        return lambda: jit_variant("fused_bc", False)(
            *args, n_rg, gl, g * gl
        )
    raise ValueError(f"unknown kernel {kernel!r}")


def _timeit(thunk, iters: int) -> tuple:
    """simple-timeit: one untimed warmup (compile), then ``iters``
    dispatches each blocked to completion -> (mean_s, best_s)."""
    import jax

    jax.block_until_ready(thunk())
    walls = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(thunk())
        walls.append(time.perf_counter() - t0)
    return sum(walls) / len(walls), min(walls)


def run_kernelbench(
    grids=DEFAULT_GRIDS, iters: int = 5, n_rg: int = 3, kernels=KERNELS,
) -> dict:
    """Run the registered kernels across ``grids`` -> the
    :data:`SCHEMA` document.  A kernel that fails to build or dispatch
    raises: a benchmark that skips a broken kernel reports nothing."""
    import jax

    rows = []
    for kernel in kernels:
        for g, gl in grids:
            mean_s, best_s = _timeit(_build(kernel, g, gl, n_rg), iters)
            rows.append({
                "kernel": kernel, "g": int(g), "gl": int(gl),
                "iters": int(iters), "mean_s": mean_s, "best_s": best_s,
            })
    return {
        "schema": SCHEMA,
        "jax_backend": jax.default_backend(),
        "rows": rows,
    }
