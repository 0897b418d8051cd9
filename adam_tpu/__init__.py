"""adam_tpu — a TPU-native genomics read-processing framework.

A from-scratch re-design of the capabilities of ADAM (the Spark/Parquet
genomics platform, see /root/reference) built idiomatically on JAX/XLA:

* Reads, variants, genotypes, features and reference fragments are
  struct-of-arrays **columnar batches** (padded + masked), not
  record-per-object Avro — so every transform is a batched array program
  that XLA can tile onto the MXU.
* The per-partition hot loops of the reference (BQSR, indel realignment,
  duplicate marking, Smith-Waterman, k-mer counting, flagstat) are JAX
  kernels: scatter-add covariate histograms, wavefront DP, segment
  reductions, packed-integer k-mer sort/unique.
* Spark's shuffle/broadcast/aggregate roles are played by XLA collectives
  (`psum`, `all_to_all`, `ppermute`) over a genome-sharded `jax.sharding.Mesh`.

Package layout (mirrors the reference's layer map, SURVEY.md §1):

* ``adam_tpu.formats``   — L0': schema constants + columnar batch types
* ``adam_tpu.models``    — L3: genomic coordinates, dictionaries, tables
* ``adam_tpu.io``        — L1/L2: SAM/BAM/FASTQ/FASTA/VCF/Parquet/2bit IO
* ``adam_tpu.ops``       — L5: pure device kernels
* ``adam_tpu.pipelines`` — L6: distributed read transforms
* ``adam_tpu.parallel``  — L4: mesh, partitioners, collective shuffles
* ``adam_tpu.api``       — L7: user-facing dataset classes + plugin API
* ``adam_tpu.cli``       — L8: command line (transform, flagstat, ...)
* ``adam_tpu.plugins``   — L7: user-plugin API (ADAMPlugin analog)
* ``adam_tpu.utils``     — L9 + misc: named-timer registry, flattener, ...
"""

import os

# pyarrow's bundled mimalloc segfaults in mi_thread_init when arrow spawns
# IO threads after short-lived Python threads that touched mimalloc TLS
# have exited (exactly the streamed transform's writer-pool shape) — pin
# the system allocator before pyarrow initializes.  io/parquet.py repeats
# this via set_memory_pool for processes that imported pyarrow first.
os.environ.setdefault("ARROW_DEFAULT_MEMORY_POOL", "system")

import jax

# Genomic coordinates, flattened genome offsets and 2-bit packed k-mers all
# need 64-bit integers (human genome ~3.1e9 bp > 2^31; k=21 k-mer = 42 bits),
# so importing adam_tpu enables jax x64 process-wide. Device arrays stay
# explicitly i32 wherever ranges allow, so unrelated JAX code keeps its
# dtypes as long as it spells them out; set ADAM_TPU_NO_X64=1 to opt out
# (k-mer packing and packed position keys then fall back to host numpy).
if not os.environ.get("ADAM_TPU_NO_X64"):
    jax.config.update("jax_enable_x64", True)

# Persistent XLA compilation cache: CLI-style invocations pay first-compile
# once per (kernel, shape) across processes, not per run.  Where
# JAX_COMPILATION_CACHE_DIR is set, JAX reads it and it alone places the
# cache; otherwise the cache sits at the fixed <checkout>/.jax_cache (the
# path is part of the cache key, so it must not move between runs).
# ADAM_TPU_NO_COMPILE_CACHE=1 turns the cache off (the tests do).
if os.environ.get("ADAM_TPU_NO_COMPILE_CACHE"):
    jax.config.update("jax_enable_compilation_cache", False)
else:
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update(
            "jax_compilation_cache_dir",
            os.path.join(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))), ".jax_cache"),
        )
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)

__version__ = "0.1.0"

from adam_tpu.formats.batch import ReadBatch  # noqa: E402,F401
from adam_tpu.models.dictionaries import (  # noqa: E402,F401
    SequenceDictionary,
    SequenceRecord,
    RecordGroupDictionary,
    RecordGroup,
)
from adam_tpu.models.positions import (  # noqa: E402,F401
    ReferencePosition,
    ReferenceRegion,
)
