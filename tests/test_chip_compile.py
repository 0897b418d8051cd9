"""Compile the main path's kernels for a described TPU v5e, without a chip.

Interpret mode and the CPU backend accept kernels that the chip's
compiler refuses (unaligned slices, 64-bit types in Mosaic, scalar VMEM
stores).  Each case here lowers one device program at read width 128
and 8,192 rows for a ``v5e:2x2`` topology that is described, not
attached, and compiles it with the TPU compiler installed here — one
to sixteen seconds each.  Nothing runs, so these say nothing about results or
times; the chip run (``chip_smoke.py``) does.

The topology is described inside a module fixture, never at import: only
the worker that runs this file loads the TPU library.
"""

import os
import re

import numpy as np
import pytest

G, GL, N_RG = 8192, 128, 3


@pytest.fixture(scope="module")
def topo():
    import jax

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a described chip's compiles cannot be read back from the cache
    jax.config.update("jax_enable_compilation_cache", False)
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    from adam_tpu.parallel.mesh import batch_mesh

    return batch_mesh(topo.devices[:4])


def _window():
    from adam_tpu.utils.kernelbench import _synth

    return _synth(G, GL, N_RG)


def _shapes(arrays, sharding):
    import jax

    return tuple(
        jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype,
                             sharding=sharding)
        for a in arrays
    )


#: An HLO instruction that sorts, scatters or gathers.
_INDEX_OP = re.compile(r"\s(sort|scatter|gather)\(")


def _compile(fn, *args, **kw):
    compiled = fn.lower(*args, **kw).compile()
    assert compiled.as_text()
    return compiled


_ROW5 = ("bases", "quals", "lengths", "flags", "read_group_idx")
_BQSR_ARGS = {
    "observe_packed": _ROW5 + ("res_bits", "mm_bits", "read_ok"),
    "apply_pack2": _ROW5 + ("has_qual", "valid", "table"),
    "fused_bc": _ROW5 + ("res_bits", "mm_bits", "read_ok", "has_qual",
                         "valid", "table"),
}
_BQSR_STATICS = {
    "observe_packed": (N_RG, GL),
    "apply_pack2": (GL, G * GL),
    "fused_bc": (N_RG, GL, G * GL),
}


def _markdup_arrays():
    from adam_tpu.formats.batch import grid_cigar_cols, pad_rows_np
    from adam_tpu.pipelines.transform_step import synthetic_batch

    b = synthetic_batch(n_reads=G, read_len=100).to_numpy()
    gc = grid_cigar_cols(b.cigar_ops.shape[1])
    return (b.start, b.end, b.flags, pad_rows_np(b.cigar_ops, G, cols=gc),
            pad_rows_np(b.cigar_lens, G, cols=gc), b.cigar_n,
            pad_rows_np(b.quals, G, cols=GL), b.lengths)


def test_markdup_columns(one_chip):
    from adam_tpu.pipelines.markdup import get_columns_jit

    _compile(get_columns_jit(), *_shapes(_markdup_arrays(), one_chip))


@pytest.mark.parametrize("donate", [False, True], ids=["keep", "donate"])
@pytest.mark.parametrize("kind", sorted(_BQSR_ARGS))
def test_bqsr_kernel(one_chip, kind, donate):
    from adam_tpu.pipelines.bqsr import jit_variant

    w = _window()
    args = _shapes([w[k] for k in _BQSR_ARGS[kind]], one_chip)
    c = _compile(jit_variant(kind, donate), *args, *_BQSR_STATICS[kind])
    ops = _INDEX_OP.findall(c.as_text())
    if kind != "observe_packed":
        # the column packs are shift networks: a u8 scatter would bring
        # back the whole-window sort the compiler puts ahead of it
        assert "sort" not in ops
    if kind.startswith("apply"):
        assert ops == ["gather"]  # the table lookup, and nothing else


def test_pack_rows_kernel(one_chip):
    """The standalone column pack holds no index op at all."""
    from adam_tpu.ops.colpack import pack_rows_kernel

    w = _window()
    args = _shapes((w["quals"], w["lengths"]), one_chip)
    assert not _INDEX_OP.search(
        _compile(pack_rows_kernel, *args, size=G * GL).as_text())


@pytest.mark.parametrize("off,rt", [(384, 16), (512, 128)])
def test_realign_sweep_gemm(one_chip, off, rt):
    """Two of the GEMM sweep's (offset, reads-per-target) tiers: the
    WGS-shaped 384 band and a deep-target tier."""
    from adam_tpu.pipelines.realign import _sweep_gemm_P, sweep_gemm_kernel

    P = _sweep_gemm_P(off, rt)
    rows = P * rt
    arrays = (np.zeros((rows, GL), np.uint8), np.zeros((rows, GL), np.uint8),
              np.zeros(rows, np.int32), np.zeros(rows, bool),
              np.zeros((P, off + GL), np.uint8), np.zeros(P, np.int32))
    _compile(sweep_gemm_kernel, *_shapes(arrays, one_chip), off, rt, GL)


def test_kmer_extract(one_chip):
    from adam_tpu.ops.kmer import extract_kmers

    w = _window()
    args = _shapes((w["bases"], w["lengths"], w["valid"]), one_chip)
    _compile(extract_kmers, *args, 21)


def _sw_inputs(sharding, b=G, lx=GL, ly=GL):
    return _shapes((np.zeros((b, lx), np.int32), np.zeros(b, np.int32),
                    np.zeros((b, ly), np.int32), np.zeros(b, np.int32)),
                   sharding)


def test_sw_score_pallas_f32(one_chip):
    from adam_tpu.ops import smith_waterman as sw

    c = _compile(sw._sw_score_pallas, *_sw_inputs(one_chip), GL, GL,
                 1.0, -0.333, -0.5, -0.5)
    assert "tpu_custom_call" in c.as_text()


def test_sw_fill_pallas(one_chip):
    from adam_tpu.ops import smith_waterman as sw

    c = _compile(sw._sw_fill_pallas, *_sw_inputs(one_chip), GL, GL,
                 1.0, -0.333, -0.5, -0.5)
    assert "tpu_custom_call" in c.as_text()


def test_health_probe(one_chip):
    """The re-admission probe's int32 matmul (a 64-bit dot does not
    compile for a TPU, and a probe that cannot run evicts its chip)."""
    import jax

    from adam_tpu.utils.health import probe_product

    x = np.zeros((64, 64), np.int32)
    _compile(jax.jit(probe_product), *_shapes((x, x), one_chip))


@pytest.mark.parametrize("kind", ["markdup", "fused_bc"])
def test_mesh_kernel_four_chips(mesh4, kind):
    """The ``--partitioner mesh`` programs over a 2x2 host's four chips:
    row-sharded windows, the table replicated, psum'd histograms."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from adam_tpu.parallel.mesh import BATCH_AXIS
    from adam_tpu.parallel.partitioner import _mesh_jit

    rows = NamedSharding(mesh4, P(BATCH_AXIS))
    if kind == "markdup":
        args = _shapes(_markdup_arrays(), rows)
        statics = {}
    else:
        w = _window()
        names = _BQSR_ARGS["fused_bc"]
        args = _shapes([w[k] for k in names[:-1]], rows) + _shapes(
            [w["table"]], NamedSharding(mesh4, P()))
        statics = {"n_rg": N_RG, "lmax": GL}
    c = _compile(_mesh_jit(kind), *args, mesh=mesh4, **statics)
    if kind == "fused_bc":
        assert "all-reduce" in c.as_text()
        assert "sort" not in _INDEX_OP.findall(c.as_text())
