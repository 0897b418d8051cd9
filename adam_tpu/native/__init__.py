"""Native (C++) ingest kernels, loaded via ctypes.

Compiled on demand with g++ from :file:`adamtok.cpp` and cached next to
the source keyed by a source hash.  Everything here degrades gracefully:
if the toolchain is unavailable or a file is malformed, callers fall back
to the pure-Python codecs (same semantics, slower).

This is the runtime layer the reference delegates to htsjdk/hadoop-bam
(JVM-native record codecs); here it is a small C++ library so host-side
ingest keeps pace with the TPU compute path.
"""

from __future__ import annotations

import ctypes as ct
import functools
import hashlib
import os
import subprocess
import tempfile
import threading
from typing import Optional, Sequence

import numpy as np

from adam_tpu.utils import instrumentation as _instr
from adam_tpu.utils import telemetry as _tele

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "adamtok.cpp")
_SRC_REALIGN = os.path.join(_DIR, "realign.cpp")
_LOCK = threading.Lock()
_LIB: Optional[ct.CDLL] = None
_LOAD_FAILED = False


def _timed(timer_name: str):
    """Record a native dispatch under the instrumentation registry (the
    InstrumentedOutputFormat analog, rdd/ADAMRDDFunctions.scala:161-164)
    AND as a telemetry span of the same name on the calling thread's
    flight-recorder track (the timer table aggregates; the span shows
    where the dispatch sat in the streamed overlap): no-op unless
    recording was switched on."""

    def deco(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            with _instr.TIMERS.time(timer_name), _tele.TRACE.span(timer_name):
                return fn(*args, **kwargs)

        return wrapper

    return deco

_i64p = ct.POINTER(ct.c_int64)
_i32p = ct.POINTER(ct.c_int32)
_u8p = ct.POINTER(ct.c_uint8)


def _cpu_fingerprint() -> str:
    """ISA feature fingerprint of this host (the 'flags' line of
    /proc/cpuinfo, or the platform string elsewhere)."""
    try:
        with open("/proc/cpuinfo") as fh:
            for line in fh:
                if line.startswith(("flags", "Features")):
                    return " ".join(sorted(line.split(":", 1)[1].split()))
    except OSError:
        pass
    import platform

    return platform.machine() + " " + platform.processor()


_BASE_FLAGS = ["-O3", "-std=c++17", "-shared", "-fPIC"]
# -march=native first: the scan/fill/LUT hot loops vectorize well on the
# AVX2/AVX-512 hosts this runs on.  The plain build is the fallback for
# toolchains/CPUs where that flag fails; the cache tag includes the flag
# set so a flag change cannot serve a stale .so.
_FLAG_SETS = [_BASE_FLAGS + ["-march=native"], _BASE_FLAGS]


@functools.lru_cache(maxsize=1)
def _compiler_fingerprint() -> str:
    """First line of ``g++ --version`` — in the cache tag so a toolchain
    upgrade (ABI/codegen change) can never serve a stale binary."""
    try:
        res = subprocess.run(
            ["g++", "--version"], capture_output=True, timeout=20
        )
        if res.returncode == 0 and res.stdout:
            return res.stdout.decode("utf-8", "replace").splitlines()[0]
    except Exception:
        pass
    return "unknown-compiler"


def _cache_dir() -> str:
    """Build-cache directory: ``ADAM_TPU_NATIVE_CACHE`` override, else
    ``_build/`` beside the sources (git-ignored), so a checkout reads
    and writes nothing outside itself."""
    return os.environ.get("ADAM_TPU_NATIVE_CACHE") or os.path.join(
        _DIR, "_build"
    )


def _build_so() -> Optional[str]:
    try:
        h = hashlib.sha256()
        for path in (_SRC, _SRC_REALIGN):
            with open(path, "rb") as fh:
                h.update(fh.read())
    except OSError:
        return None  # missing source: degrade to the Python fallbacks
    h.update(_compiler_fingerprint().encode())
    src_hash = h.copy()
    build_dir = _cache_dir()
    for flags in _FLAG_SETS:
        h = src_hash.copy()
        h.update(" ".join(flags).encode())
        if "-march=native" in flags:
            # a native-ISA binary is host-specific: key the cache on the
            # CPU's feature set so a shared cache dir can never serve an
            # AVX-512 build to a host that would SIGILL on it
            h.update(_cpu_fingerprint().encode())
        tag = h.hexdigest()[:16]
        so_path = os.path.join(build_dir, f"adamtok_{tag}.so")
        if os.path.exists(so_path):
            return so_path
        try:
            os.makedirs(build_dir, exist_ok=True)
            with tempfile.TemporaryDirectory(dir=build_dir) as td:
                tmp = os.path.join(td, "adamtok.so")
                cmd = (
                    ["g++"] + flags
                    + ["-o", tmp, _SRC, _SRC_REALIGN, "-lz", "-pthread"]
                )
                res = subprocess.run(cmd, capture_output=True, timeout=240)
                if res.returncode != 0:
                    continue
                os.replace(tmp, so_path)
            return so_path
        except Exception:
            continue
    return None


def _lib() -> Optional[ct.CDLL]:
    global _LIB, _LOAD_FAILED
    if _LIB is not None or _LOAD_FAILED:
        return _LIB
    with _LOCK:
        if _LIB is not None or _LOAD_FAILED:
            return _LIB
        so = _build_so()
        if so is None:
            _LOAD_FAILED = True
            return None
        try:
            lib = ct.CDLL(so)
            lib.adamtok_version.restype = ct.c_int
            lib.samtok_scan.restype = ct.c_void_p
            lib.samtok_scan.argtypes = [_u8p, ct.c_int64, ct.c_int64, ct.c_int]
            lib.samtok_dims.argtypes = [
                ct.c_void_p, _i64p, _i32p, _i32p, _i64p, _i64p,
            ]
            lib.samtok_fill.restype = ct.c_int
            _out_cols = [
                _i32p, _i32p, _i64p, _i64p, _i32p, _i32p, _i64p, _i32p,
                _i32p, _i32p, _u8p,                     # ...has_qual
                _u8p, _u8p, ct.c_int64,                 # bases, quals, lmax
                _u8p, _i32p, _i32p, ct.c_int64,         # cigar_*, cmax
                _u8p, _i64p,                            # name
                _u8p, _i64p,                            # attrs
                _u8p, _i64p, _u8p,                      # md
                _u8p, _i64p, _u8p,                      # oq
                _i64p, _i64p, _i64p,                    # byte counts out
            ]
            lib.samtok_fill.argtypes = (
                [ct.c_void_p, _u8p, _i64p, ct.c_int32, _u8p, _i64p,
                 ct.c_int32] + _out_cols
            )
            lib.samtok_free.argtypes = [ct.c_void_p]
            lib.bgzf_scan.restype = ct.c_void_p
            lib.bgzf_scan.argtypes = [_u8p, ct.c_int64]
            lib.bgzf_dims.argtypes = [ct.c_void_p, _i64p, _i64p]
            lib.bgzf_fill.restype = ct.c_int
            lib.bgzf_fill.argtypes = [ct.c_void_p, _u8p, ct.c_int]
            lib.bgzf_free.argtypes = [ct.c_void_p]
            lib.bgzf_compress.restype = ct.c_int
            lib.bgzf_compress.argtypes = [
                _u8p, ct.c_int64, ct.c_int64, _u8p, ct.c_int64, _i64p,
                ct.c_int, ct.c_int,
            ]
            lib.bamtok_scan.restype = ct.c_void_p
            lib.bamtok_scan.argtypes = [_u8p, ct.c_int64, ct.c_int64]
            lib.bamtok_dims.argtypes = [
                ct.c_void_p, _i64p, _i32p, _i32p, _i64p, _i64p,
            ]
            lib.bamtok_fill.restype = ct.c_int
            lib.bamtok_fill.argtypes = (
                [ct.c_void_p, _u8p, _i64p, ct.c_int32] + _out_cols
                + [ct.c_int]
            )
            lib.bamtok_free.argtypes = [ct.c_void_p]
            lib.bgzf_scan2.restype = ct.c_void_p
            lib.bgzf_scan2.argtypes = [_u8p, ct.c_int64, ct.c_int]
            lib.bgzf_consumed.restype = ct.c_int64
            lib.bgzf_consumed.argtypes = [ct.c_void_p]
            lib.bamtok_scan2.restype = ct.c_void_p
            lib.bamtok_scan2.argtypes = [
                _u8p, ct.c_int64, ct.c_int64, ct.c_int,
            ]
            lib.bamtok_consumed.restype = ct.c_int64
            lib.bamtok_consumed.argtypes = [ct.c_void_p]
            lib.ref_positions.argtypes = [
                _u8p, _i32p, _i32p, _i64p,
                ct.c_int64, ct.c_int64, ct.c_int64, _i64p, ct.c_int,
            ]
            lib.cigar_cols.restype = ct.c_int
            lib.cigar_cols.argtypes = [
                _u8p, _i64p, ct.c_int64, ct.c_int64,
                _u8p, _i32p, _i32p, ct.c_int,
            ]
            lib.bqsr_observe.argtypes = [
                _u8p, _u8p, _i32p, _i32p, _i32p,
                _u8p, _i32p, _i32p, ct.c_int64,
                _i32p, _i64p, _i64p, ct.c_int64,
                _u8p, _u8p, _u8p,
                _u8p, _i64p,
                ct.c_int64, ct.c_int64, ct.c_int32, ct.c_int64,
                _i64p, _i64p, ct.c_int,
            ]
            lib.cigar_strings.restype = ct.c_int64
            lib.cigar_strings.argtypes = [
                _u8p, _i32p, _i32p, ct.c_int64, ct.c_int64,
                _u8p, ct.c_int64, _i64p, ct.c_int,
            ]
            lib.fastq_encode.restype = ct.c_int64
            lib.fastq_encode.argtypes = [
                _i32p, _i32p, _u8p, _u8p, _u8p, ct.c_int64,
                _u8p, _i64p, ct.c_int, ct.c_int64, _u8p, ct.c_int64,
                ct.c_int,
            ]
            lib.bqsr_apply.argtypes = [
                _u8p, _u8p, _i32p, _i32p, _i32p, _u8p, _u8p,
                ct.c_int64, ct.c_int64,
                _u8p, ct.c_int32, ct.c_int32, ct.c_int64,
                _u8p, ct.c_int,
            ]
            lib.sam_encode.restype = ct.c_int64
            lib.sam_encode.argtypes = [
                _i32p, _i32p, _i64p, _i32p, _i32p, _i64p, _i32p, _i32p,
                _u8p, _u8p,
                _u8p, _u8p, ct.c_int64,
                _u8p, _i32p, _i32p, ct.c_int64,
                _u8p, _i64p,
                _u8p, _i64p,
                _u8p, _i64p, _u8p,
                _u8p, _i64p, _u8p,
                _i32p, _u8p, _i64p, ct.c_int32,
                _u8p, _i64p, ct.c_int32,
                ct.c_int64, _u8p, ct.c_int64, ct.c_int,
            ]
            lib.bam_encode.restype = ct.c_int64
            lib.bam_encode.argtypes = [
                _i32p, _i32p, _i64p, _i32p, _i32p, _i64p, _i32p, _i32p,
                _u8p, _u8p,
                _u8p, _u8p, ct.c_int64,
                _u8p, _i32p, _i32p, ct.c_int64,
                _u8p, _i64p,
                _u8p, _i64p,
                _u8p, _i64p, _u8p,
                _u8p, _i64p, _u8p,
                _i32p, _u8p, _i64p, ct.c_int32, ct.c_int32,
                ct.c_int64, _u8p, ct.c_int64, ct.c_int,
            ]
            lib.span_gather.argtypes = [_u8p, _i64p, _i64p, ct.c_int64, _u8p]
            lib.span_gather_strided.argtypes = [
                _u8p, _i64p, _i64p, ct.c_int64, ct.c_int64, _u8p,
            ]
            lib.lut_compact_rows.argtypes = [
                _u8p, _i32p, _i64p, ct.c_int64, ct.c_int64, _u8p, _u8p,
                ct.c_int,
            ]
            lib.line_index_strided.restype = ct.c_int64
            lib.line_index_strided.argtypes = [
                _u8p, ct.c_int64, ct.c_int64, ct.c_int64, _i64p, ct.c_int64,
            ]
            lib.realign_prep.restype = ct.c_void_p
            lib.realign_prep.argtypes = [
                _u8p, _u8p, ct.c_int64, ct.c_int64,            # bases/quals/N/L
                _i32p, _i64p,                                  # lengths/start
                _u8p, _i32p, _i32p, ct.c_int64,                # cigar cols + C
                _u8p, _i64p, _u8p,                             # md buf/off/valid
                _i64p, _i64p, ct.c_int64,                      # grows/goff/G
                ct.c_int,                                      # gen_consensus
            ]
            lib.realign_prep_dims.argtypes = [
                ct.c_void_p, _i64p, _i64p, _i64p, _i64p, _i64p, _i64p,
                _i64p, _i64p,
            ]
            lib.realign_prep_fill.argtypes = [
                ct.c_void_p,
                _i32p, _u8p, _i64p, _i64p, _i64p,              # targets
                _i32p, _i64p, _u8p, _i64p, _u8p, _i64p, _u8p,  # reads
                _u8p, _u8p, _i64p,
                _i32p, _u8p, _i64p, _i64p, _i64p,              # consensuses
            ]
            lib.realign_prep_free.argtypes = [ct.c_void_p]
            lib.md_move_batch.restype = ct.c_int64
            lib.md_move_batch.argtypes = [
                _u8p, ct.c_int64, ct.c_int64, _i32p,
                _i64p, ct.c_int64,
                _u8p, _i64p,
                _i32p, _i64p,
                _i32p, _i32p, _u8p, _i32p, _i64p,
                _u8p, ct.c_int64, _i64p,
                _i64p, _i64p,
            ]
            _LIB = lib
        except Exception:
            _LOAD_FAILED = True
    return _LIB


def available() -> bool:
    return _lib() is not None


def _nthreads() -> int:
    env = os.environ.get("ADAM_TPU_NATIVE_THREADS")
    if env:
        return max(1, int(env))
    return max(1, min(16, os.cpu_count() or 1))


def _as_u8(data) -> np.ndarray:
    if isinstance(data, (bytes, bytearray, memoryview)):
        return np.frombuffer(data, dtype=np.uint8)
    return np.ascontiguousarray(data, dtype=np.uint8)


def _pretouch(arr: np.ndarray) -> np.ndarray:
    """Fault in a fresh allocation's pages single-threaded before handing
    it to the threaded C++ fills: concurrent first-touch faults from many
    threads serialize on the kernel's mmap lock (measured: a fresh 3.2 GB
    output faulted by 16 threads took 60 s vs 0.75 s pre-touched)."""
    flat = arr.reshape(-1).view(np.uint8)
    if flat.nbytes >= 1 << 20:
        flat[:: 4096] = 0
    return arr


_DUMMY = np.zeros(1, np.uint8)  # stand-in pointer for zero-size buffers


def _u8_ptr(a: np.ndarray):
    if len(a) == 0:
        a = _DUMMY
    return a.ctypes.data_as(_u8p)


def _str_dict(names: Sequence[str]) -> tuple[np.ndarray, np.ndarray]:
    from adam_tpu.formats.strings import StringColumn

    c = StringColumn.from_list(list(names))
    return c.buf, c.offsets


@_timed(_instr.TOKENIZE_INPUT)
def tokenize_sam(data, body_off: int, contig_names: Sequence[str],
                 rg_names: Sequence[str]) -> Optional[dict]:
    """Tokenize SAM body lines into columnar arrays; None -> fall back."""
    lib = _lib()
    if lib is None:
        return None
    buf = _as_u8(data)
    h = lib.samtok_scan(_u8_ptr(buf), len(buf), body_off, _nthreads())
    if not h:
        return None
    try:
        n = ct.c_int64()
        lmax = ct.c_int32()
        cmax = ct.c_int32()
        nameb = ct.c_int64()
        tagb = ct.c_int64()
        lib.samtok_dims(h, ct.byref(n), ct.byref(lmax), ct.byref(cmax),
                        ct.byref(nameb), ct.byref(tagb))
        n, L, C = n.value, max(1, lmax.value), max(1, cmax.value)
        nameb, tagb = nameb.value, tagb.value

        out = _alloc_columns(n, L, C, nameb, tagb)
        cbuf, coff = _str_dict(contig_names)
        gbuf, goff = _str_dict(rg_names)
        ab = ct.c_int64()
        mb = ct.c_int64()
        qb = ct.c_int64()
        rc = lib.samtok_fill(
            h,
            _u8_ptr(cbuf), coff.ctypes.data_as(_i64p), len(contig_names),
            _u8_ptr(gbuf), goff.ctypes.data_as(_i64p), len(rg_names),
            out["flags"].ctypes.data_as(_i32p),
            out["contig_idx"].ctypes.data_as(_i32p),
            out["start"].ctypes.data_as(_i64p),
            out["end"].ctypes.data_as(_i64p),
            out["mapq"].ctypes.data_as(_i32p),
            out["mate_contig_idx"].ctypes.data_as(_i32p),
            out["mate_start"].ctypes.data_as(_i64p),
            out["tlen"].ctypes.data_as(_i32p),
            out["rg_idx"].ctypes.data_as(_i32p),
            out["lengths"].ctypes.data_as(_i32p),
            _u8_ptr(out["has_qual"]),
            _u8_ptr(out["bases"].reshape(-1)), _u8_ptr(out["quals"].reshape(-1)),
            ct.c_int64(L),
            _u8_ptr(out["cigar_ops"].reshape(-1)),
            out["cigar_lens"].ctypes.data_as(_i32p),
            out["cigar_n"].ctypes.data_as(_i32p),
            ct.c_int64(C),
            _u8_ptr(out["name_buf"]), out["name_off"].ctypes.data_as(_i64p),
            _u8_ptr(out["attr_buf"]), out["attr_off"].ctypes.data_as(_i64p),
            _u8_ptr(out["md_buf"]), out["md_off"].ctypes.data_as(_i64p),
            _u8_ptr(out["md_present"]),
            _u8_ptr(out["oq_buf"]), out["oq_off"].ctypes.data_as(_i64p),
            _u8_ptr(out["oq_present"]),
            ct.byref(ab), ct.byref(mb), ct.byref(qb),
        )
        if rc != 0:
            return None
        out["attr_buf"] = out["attr_buf"][: ab.value]
        out["md_buf"] = out["md_buf"][: mb.value]
        out["oq_buf"] = out["oq_buf"][: qb.value]
        return out
    finally:
        lib.samtok_free(h)


def _alloc_columns(n: int, L: int, C: int, nameb: int, tagb: int) -> dict:
    out = dict(
        n=n, lmax=L, cmax=C,
        flags=np.empty(n, np.int32),
        contig_idx=np.empty(n, np.int32),
        start=np.empty(n, np.int64),
        end=np.empty(n, np.int64),
        mapq=np.empty(n, np.int32),
        mate_contig_idx=np.empty(n, np.int32),
        mate_start=np.empty(n, np.int64),
        tlen=np.empty(n, np.int32),
        rg_idx=np.empty(n, np.int32),
        lengths=np.empty(n, np.int32),
        has_qual=np.empty(n, np.uint8),
        bases=np.empty((n, L), np.uint8),
        quals=np.empty((n, L), np.uint8),
        cigar_ops=np.empty((n, C), np.uint8),
        cigar_lens=np.empty((n, C), np.int32),
        cigar_n=np.empty(n, np.int32),
        name_buf=np.empty(max(1, nameb), np.uint8)[:nameb],
        name_off=np.empty(n + 1, np.int64),
        attr_buf=np.empty(max(1, tagb), np.uint8),
        attr_off=np.empty(n + 1, np.int64),
        md_buf=np.empty(max(1, tagb), np.uint8),
        md_off=np.empty(n + 1, np.int64),
        md_present=np.empty(n, np.uint8),
        oq_buf=np.empty(max(1, tagb), np.uint8),
        oq_off=np.empty(n + 1, np.int64),
        oq_present=np.empty(n, np.uint8),
    )
    for v in out.values():
        if isinstance(v, np.ndarray):
            _pretouch(v)
    return out


@_timed(_instr.BGZF_CODEC)
def bgzf_decompress(data) -> Optional[bytes]:
    """Block-parallel BGZF decode; None if not BGZF / native unavailable."""
    lib = _lib()
    if lib is None:
        return None
    buf = _as_u8(data)
    h = lib.bgzf_scan(_u8_ptr(buf), len(buf))
    if not h:
        return None
    try:
        nb = ct.c_int64()
        ob = ct.c_int64()
        lib.bgzf_dims(h, ct.byref(nb), ct.byref(ob))
        out = _pretouch(np.empty(max(1, ob.value), np.uint8))
        if lib.bgzf_fill(h, _u8_ptr(out), _nthreads()) != 0:
            return None
        return out[: ob.value].tobytes()
    finally:
        lib.bgzf_free(h)


@_timed(_instr.BGZF_CODEC)
def bgzf_decompress_partial(data) -> Optional[tuple[bytes, int]]:
    """Streaming-window BGZF decode: decompress the *complete* blocks in
    ``data`` -> (decompressed bytes, input bytes consumed); a truncated
    final block is left for the caller's next window.  None if the data
    is not BGZF or the native library is unavailable."""
    lib = _lib()
    if lib is None:
        return None
    buf = _as_u8(data)
    h = lib.bgzf_scan2(_u8_ptr(buf), len(buf), 1)
    if not h:
        return None
    try:
        nb = ct.c_int64()
        ob = ct.c_int64()
        lib.bgzf_dims(h, ct.byref(nb), ct.byref(ob))
        out = _pretouch(np.empty(max(1, ob.value), np.uint8))
        if lib.bgzf_fill(h, _u8_ptr(out), _nthreads()) != 0:
            return None
        return out[: ob.value].tobytes(), int(lib.bgzf_consumed(h))
    finally:
        lib.bgzf_free(h)


@_timed(_instr.BGZF_CODEC)
def bgzf_compress(
    data, level: int = 6, block_size: int = 0xFF00
) -> Optional[bytes]:
    """Block-parallel BGZF encode (+EOF block); None if unavailable."""
    lib = _lib()
    if lib is None:
        return None
    buf = _as_u8(data)
    n = len(buf)
    block = min(max(1, block_size), 0xFF00)  # BSIZE is a u16 total-size field
    n_blocks = (n + block - 1) // block if n else 0
    cap = n + n_blocks * 64 + n // 512 + 1024
    out = np.empty(cap, np.uint8)
    out_len = ct.c_int64()
    rc = lib.bgzf_compress(
        _u8_ptr(buf), ct.c_int64(n), ct.c_int64(block), _u8_ptr(out),
        ct.c_int64(cap), ct.byref(out_len), ct.c_int(_nthreads()),
        ct.c_int(level),
    )
    if rc != 0:
        return None
    return out[: out_len.value].tobytes()


@_timed(_instr.TOKENIZE_INPUT)
def tokenize_bam(raw, records_off: int,
                 rg_names: Sequence[str],
                 partial: bool = False) -> Optional[dict]:
    """Parse decompressed BAM records into columnar arrays.

    With ``partial=True`` (streaming windows) a record truncated at the
    end of ``raw`` stops the scan instead of failing, and the result
    carries ``out["consumed"]`` — the byte offset after the last
    complete record — so the caller can carry the tail into the next
    window."""
    lib = _lib()
    if lib is None:
        return None
    buf = _as_u8(raw)
    h = lib.bamtok_scan2(_u8_ptr(buf), len(buf), records_off,
                         1 if partial else 0)
    if not h:
        return None
    try:
        n = ct.c_int64()
        lmax = ct.c_int32()
        cmax = ct.c_int32()
        nameb = ct.c_int64()
        tagb = ct.c_int64()
        lib.bamtok_dims(h, ct.byref(n), ct.byref(lmax), ct.byref(cmax),
                        ct.byref(nameb), ct.byref(tagb))
        n, L, C = n.value, max(1, lmax.value), max(1, cmax.value)
        out = _alloc_columns(n, L, C, nameb.value, tagb.value)
        gbuf, goff = _str_dict(rg_names)
        ab = ct.c_int64()
        mb = ct.c_int64()
        qb = ct.c_int64()
        rc = lib.bamtok_fill(
            h,
            _u8_ptr(gbuf), goff.ctypes.data_as(_i64p), len(rg_names),
            out["flags"].ctypes.data_as(_i32p),
            out["contig_idx"].ctypes.data_as(_i32p),
            out["start"].ctypes.data_as(_i64p),
            out["end"].ctypes.data_as(_i64p),
            out["mapq"].ctypes.data_as(_i32p),
            out["mate_contig_idx"].ctypes.data_as(_i32p),
            out["mate_start"].ctypes.data_as(_i64p),
            out["tlen"].ctypes.data_as(_i32p),
            out["rg_idx"].ctypes.data_as(_i32p),
            out["lengths"].ctypes.data_as(_i32p),
            _u8_ptr(out["has_qual"]),
            _u8_ptr(out["bases"].reshape(-1)), _u8_ptr(out["quals"].reshape(-1)),
            ct.c_int64(L),
            _u8_ptr(out["cigar_ops"].reshape(-1)),
            out["cigar_lens"].ctypes.data_as(_i32p),
            out["cigar_n"].ctypes.data_as(_i32p),
            ct.c_int64(C),
            _u8_ptr(out["name_buf"]), out["name_off"].ctypes.data_as(_i64p),
            _u8_ptr(out["attr_buf"]), out["attr_off"].ctypes.data_as(_i64p),
            _u8_ptr(out["md_buf"]), out["md_off"].ctypes.data_as(_i64p),
            _u8_ptr(out["md_present"]),
            _u8_ptr(out["oq_buf"]), out["oq_off"].ctypes.data_as(_i64p),
            _u8_ptr(out["oq_present"]),
            ct.byref(ab), ct.byref(mb), ct.byref(qb),
            ct.c_int(_nthreads()),
        )
        if rc != 0:
            return None
        out["attr_buf"] = out["attr_buf"][: ab.value]
        out["md_buf"] = out["md_buf"][: mb.value]
        out["oq_buf"] = out["oq_buf"][: qb.value]
        out["consumed"] = int(lib.bamtok_consumed(h))
        return out
    finally:
        lib.bamtok_free(h)


def ref_positions(cigar_ops, cigar_lens, cigar_n, start, lmax: int):
    """Per-base reference positions -> i64[N, lmax]; None if native
    unavailable.

    Threaded C++ CIGAR walk; the fallback is
    :func:`adam_tpu.ops.cigar.reference_positions_np`.
    """
    lib = _lib()
    if lib is None:
        return None
    ops = np.ascontiguousarray(cigar_ops, np.uint8)
    lens = np.ascontiguousarray(cigar_lens, np.int32)
    n_ops = np.ascontiguousarray(cigar_n, np.int32)
    st = np.ascontiguousarray(start, np.int64)
    N, C = ops.shape
    out = _pretouch(np.empty((N, lmax), np.int64))
    lib.ref_positions(
        _u8_ptr(ops), lens.ctypes.data_as(_i32p), n_ops.ctypes.data_as(_i32p),
        st.ctypes.data_as(_i64p),
        ct.c_int64(N), ct.c_int64(C), ct.c_int64(lmax),
        out.ctypes.data_as(_i64p), ct.c_int(_nthreads()),
    )
    return out


def cigar_cols(buf: np.ndarray, offsets: np.ndarray, cmax: int):
    """CIGAR strings (flat u8 buffer + offsets) -> (ops u8[N, C],
    lens i32[N, C], n_ops i32[N]); None if native unavailable or any row
    overflows ``cmax``."""
    lib = _lib()
    if lib is None:
        return None
    buf = np.ascontiguousarray(buf, np.uint8)
    offsets = np.ascontiguousarray(offsets, np.int64)
    n = len(offsets) - 1
    C = max(1, int(cmax))
    ops = np.empty((n, C), np.uint8)
    lens = np.empty((n, C), np.int32)
    n_ops = np.empty(n, np.int32)
    rc = lib.cigar_cols(
        _u8_ptr(buf), offsets.ctypes.data_as(_i64p),
        ct.c_int64(n), ct.c_int64(C),
        _u8_ptr(ops.reshape(-1)), lens.ctypes.data_as(_i32p),
        n_ops.ctypes.data_as(_i32p), ct.c_int(_nthreads()),
    )
    if rc != 0:
        return None
    return ops, lens, n_ops


def _encode_prep(batch, side, rg_names: Sequence[str]):
    """Shared marshalling for the SAM/BAM encoders: numpy-ified batch,
    sidecar StringColumns (None when the sidecar is shorter than the
    padded batch -> caller falls back), RG dict, and the common leading
    ctypes argument list."""
    import jax

    from adam_tpu.formats.strings import StringColumn

    b = jax.tree.map(lambda x: np.asarray(x), batch)
    n = b.n_rows
    names = StringColumn.of(side.names)
    attrs = StringColumn.of(side.attrs)
    md = StringColumn.of(side.md)
    oq = StringColumn.of(side.orig_quals)
    if len(names) < n or len(attrs) < n or len(md) < n or len(oq) < n:
        return None

    c64 = lambda x: np.ascontiguousarray(x, np.int64)  # noqa: E731
    c32 = lambda x: np.ascontiguousarray(x, np.int32)  # noqa: E731
    cu8 = lambda x: np.ascontiguousarray(x, np.uint8)  # noqa: E731

    gbuf, goff = _str_dict(rg_names)
    # keep every marshalled array alive for the duration of the call
    keep = dict(
        flags=c32(b.flags), contig_idx=c32(b.contig_idx), start=c64(b.start),
        mapq=c32(b.mapq), mate_contig_idx=c32(b.mate_contig_idx),
        mate_start=c64(b.mate_start), tlen=c32(b.tlen),
        lengths=c32(b.lengths), has_qual=cu8(np.asarray(b.has_qual)),
        valid=cu8(np.asarray(b.valid)),
        bases=cu8(b.bases).reshape(-1), quals=cu8(b.quals).reshape(-1),
        cigar_ops=cu8(b.cigar_ops).reshape(-1),
        cigar_lens=c32(b.cigar_lens), cigar_n=c32(b.cigar_n),
        md_valid=cu8(np.asarray(md.valid)),
        oq_valid=cu8(np.asarray(oq.valid) & (oq.lengths() > 0)),
        rg_idx=c32(b.read_group_idx), gbuf=gbuf, goff=goff,
    )
    args = [
        keep["flags"].ctypes.data_as(_i32p),
        keep["contig_idx"].ctypes.data_as(_i32p),
        keep["start"].ctypes.data_as(_i64p),
        keep["mapq"].ctypes.data_as(_i32p),
        keep["mate_contig_idx"].ctypes.data_as(_i32p),
        keep["mate_start"].ctypes.data_as(_i64p),
        keep["tlen"].ctypes.data_as(_i32p),
        keep["lengths"].ctypes.data_as(_i32p),
        _u8_ptr(keep["has_qual"]),
        _u8_ptr(keep["valid"]),
        _u8_ptr(keep["bases"]),
        _u8_ptr(keep["quals"]),
        ct.c_int64(b.lmax),
        _u8_ptr(keep["cigar_ops"]),
        keep["cigar_lens"].ctypes.data_as(_i32p),
        keep["cigar_n"].ctypes.data_as(_i32p),
        ct.c_int64(b.cmax),
        _u8_ptr(names.buf), names.offsets.ctypes.data_as(_i64p),
        _u8_ptr(attrs.buf), attrs.offsets.ctypes.data_as(_i64p),
        _u8_ptr(md.buf), md.offsets.ctypes.data_as(_i64p),
        _u8_ptr(keep["md_valid"]),
        _u8_ptr(oq.buf), oq.offsets.ctypes.data_as(_i64p),
        _u8_ptr(keep["oq_valid"]),
        keep["rg_idx"].ctypes.data_as(_i32p),
        _u8_ptr(gbuf), goff.ctypes.data_as(_i64p), ct.c_int32(len(rg_names)),
    ]
    # common capacity terms: names + cigars + seq/qual + sidecar strings
    lens = np.where(b.valid, b.lengths, 0).astype(np.int64)
    base_cap = (
        int(names.offsets[-1])
        + 12 * int(np.asarray(b.cigar_n, np.int64).sum())
        + int(lens.sum()) * 2
        + int(attrs.offsets[-1]) + int(md.offsets[-1]) + int(oq.offsets[-1])
        + (max((len(s) for s in rg_names), default=0) + 8) * n
    )
    keep["_strings"] = (names, attrs, md, oq)
    return n, args, base_cap, keep


@_timed(_instr.SAM_ENCODE)
def bam_encode(batch, side, rg_names: Sequence[str],
               n_refs: int) -> Optional[bytes]:
    """Encode a (ReadBatch, ReadSidecar) into the BAM record stream
    (everything after the reference list); None -> caller falls back to
    the pure-Python writer.  ``n_refs`` bounds contig/mate refIDs — an
    out-of-range index fails the encode rather than emitting a BAM whose
    refID points outside the reference list."""
    lib = _lib()
    if lib is None:
        return None
    prep = _encode_prep(batch, side, rg_names)
    if prep is None:
        return None
    n, args, base_cap, keep = prep
    cap = int(n * 80 + base_cap)
    out = _pretouch(np.empty(cap, np.uint8))
    got = lib.bam_encode(
        *args, ct.c_int32(int(n_refs)), ct.c_int64(n), _u8_ptr(out),
        ct.c_int64(cap), ct.c_int(_nthreads()),
    )
    if got < 0:
        return None
    return out[:got].tobytes()


@_timed(_instr.SAM_ENCODE)
def sam_encode(batch, side, rg_names: Sequence[str],
               contig_names: Sequence[str]) -> Optional[bytes]:
    """Format a (ReadBatch, ReadSidecar) as SAM text lines (no header);
    None -> caller falls back to the pure-Python formatter."""
    lib = _lib()
    if lib is None:
        return None
    prep = _encode_prep(batch, side, rg_names)
    if prep is None:
        return None
    n, args, base_cap, keep = prep
    cbuf, coff = _str_dict(contig_names)
    max_name = (max((len(s) for s in contig_names), default=1) + 2) * 2
    cap = int(n * (140 + max_name) + base_cap)
    out = _pretouch(np.empty(cap, np.uint8))
    got = lib.sam_encode(
        *args,
        _u8_ptr(cbuf), coff.ctypes.data_as(_i64p),
        ct.c_int32(len(contig_names)),
        ct.c_int64(n), _u8_ptr(out), ct.c_int64(cap), ct.c_int(_nthreads()),
    )
    if got < 0:
        return None
    return out[:got].tobytes()


@_timed(_instr.APPLY_WALK)
def bqsr_apply(bases, quals, lengths, flags, rg_idx, has_qual, valid,
               table_u8, gl: int):
    """Threaded host application of the BQSR recalibration table ->
    new quals u8[N, L]; None if native unavailable."""
    lib = _lib()
    if lib is None:
        return None
    bases = np.ascontiguousarray(bases, np.uint8)
    quals = np.ascontiguousarray(quals, np.uint8)
    n, lmax = bases.shape
    table = np.ascontiguousarray(table_u8, np.uint8)
    n_rg, _, n_cyc, _ = table.shape
    out = _pretouch(np.empty((n, lmax), np.uint8))
    lib.bqsr_apply(
        _u8_ptr(bases.reshape(-1)), _u8_ptr(quals.reshape(-1)),
        np.ascontiguousarray(lengths, np.int32).ctypes.data_as(_i32p),
        np.ascontiguousarray(flags, np.int32).ctypes.data_as(_i32p),
        np.ascontiguousarray(rg_idx, np.int32).ctypes.data_as(_i32p),
        _u8_ptr(np.ascontiguousarray(has_qual, np.uint8)),
        _u8_ptr(np.ascontiguousarray(valid, np.uint8)),
        ct.c_int64(n), ct.c_int64(lmax),
        _u8_ptr(table.reshape(-1)), ct.c_int32(n_rg), ct.c_int32(n_cyc),
        ct.c_int64(gl), _u8_ptr(out.reshape(-1)), ct.c_int(_nthreads()),
    )
    return out


@_timed(_instr.OBSERVE_WALK)
def bqsr_observe(bases, quals, lengths, flags, rg_idx,
                 cigar_ops, cigar_lens, cigar_n,
                 residue_ok, is_mm, read_ok, n_rg: int, gl: int,
                 contig_idx=None, start=None, snp_keys=None,
                 md_buf=None, md_off=None):
    """Threaded host covariate histogram -> (total, mism) i64 arrays of
    shape [n_rg, 94, 2*gl+1, 17]; None if native unavailable.

    ``residue_ok`` may be None: the aligned/q>0/base<4 residue filter is
    then derived from the cigar columns inside the kernel, so no [N, L]
    mask ever materializes.  ``is_mm`` may also be None when
    ``md_buf``/``md_off`` (the sidecar MD string column) are given: the
    kernel parses each read's MD inline during the same walk instead of
    consuming a host-tokenized [N, L] mismatch mask.  Known-SNP masking
    likewise runs in-kernel:
    pass ``contig_idx``/``start`` plus ``snp_keys`` (sorted i64
    ``contig << 40 | pos`` site keys) and masked residues are skipped
    during the same cigar walk — no host-side [N, L] position matrix."""
    lib = _lib()
    if lib is None:
        return None
    bases = np.ascontiguousarray(bases, np.uint8)
    quals = np.ascontiguousarray(quals, np.uint8)
    n, lmax = bases.shape
    c_ops = np.ascontiguousarray(cigar_ops, np.uint8)
    cmax = c_ops.shape[1] if c_ops.ndim == 2 else 0
    n_cyc = 2 * gl + 1
    shape = (n_rg, 94, n_cyc, 17)
    total = _pretouch(np.empty(shape, np.int64))
    mism = _pretouch(np.empty(shape, np.int64))
    if residue_ok is not None:
        rok_arr = np.ascontiguousarray(residue_ok, np.uint8).reshape(-1)
        rok_ptr = _u8_ptr(rok_arr)
    else:
        rok_ptr = ct.cast(None, _u8p)
    if snp_keys is not None and len(snp_keys) and residue_ok is None:
        ci_arr = np.ascontiguousarray(contig_idx, np.int32)
        st_arr = np.ascontiguousarray(start, np.int64)
        sk_arr = np.ascontiguousarray(snp_keys, np.int64)
        ci_ptr = ci_arr.ctypes.data_as(_i32p)
        st_ptr = st_arr.ctypes.data_as(_i64p)
        sk_ptr = sk_arr.ctypes.data_as(_i64p)
        n_snps = len(sk_arr)
    else:
        ci_ptr = ct.cast(None, _i32p)
        st_ptr = ct.cast(None, _i64p)
        sk_ptr = ct.cast(None, _i64p)
        n_snps = 0
    if is_mm is not None:
        mm_arr = np.ascontiguousarray(is_mm, np.uint8).reshape(-1)
        mm_ptr = _u8_ptr(mm_arr)
        mdb_ptr = ct.cast(None, _u8p)
        mdo_ptr = ct.cast(None, _i64p)
    else:
        if md_buf is None or md_off is None:
            return None
        mdb_arr = np.ascontiguousarray(md_buf, np.uint8)
        mdo_arr = np.ascontiguousarray(md_off, np.int64)
        mm_ptr = ct.cast(None, _u8p)
        mdb_ptr = _u8_ptr(mdb_arr)
        mdo_ptr = mdo_arr.ctypes.data_as(_i64p)
    lib.bqsr_observe(
        _u8_ptr(bases.reshape(-1)), _u8_ptr(quals.reshape(-1)),
        np.ascontiguousarray(lengths, np.int32).ctypes.data_as(_i32p),
        np.ascontiguousarray(flags, np.int32).ctypes.data_as(_i32p),
        np.ascontiguousarray(rg_idx, np.int32).ctypes.data_as(_i32p),
        _u8_ptr(c_ops.reshape(-1)),
        np.ascontiguousarray(cigar_lens, np.int32).ctypes.data_as(_i32p),
        np.ascontiguousarray(cigar_n, np.int32).ctypes.data_as(_i32p),
        ct.c_int64(cmax),
        ci_ptr, st_ptr, sk_ptr, ct.c_int64(n_snps),
        rok_ptr,
        mm_ptr,
        _u8_ptr(np.ascontiguousarray(read_ok, np.uint8)),
        mdb_ptr, mdo_ptr,
        ct.c_int64(n), ct.c_int64(lmax), ct.c_int32(n_rg), ct.c_int64(gl),
        total.ctypes.data_as(_i64p), mism.ctypes.data_as(_i64p),
        ct.c_int(_nthreads()),
    )
    return total, mism


@_timed(_instr.FASTQ_ENCODE)
def fastq_encode(batch, side, select, add_suffix: bool) -> Optional[bytes]:
    """Format selected rows as FASTQ text; None -> python fallback."""
    lib = _lib()
    if lib is None:
        return None
    import jax

    from adam_tpu.formats.strings import StringColumn

    b = jax.tree.map(lambda x: np.asarray(x), batch)
    n = b.n_rows
    names = StringColumn.of(side.names)
    if len(names) < n:
        return None
    lens = np.where(select, b.lengths, 0).astype(np.int64)
    cap = int(int(names.offsets[-1]) + 2 * int(lens.sum()) + 16 * n + 64)
    out = _pretouch(np.empty(cap, np.uint8))
    got = lib.fastq_encode(
        np.ascontiguousarray(b.flags, np.int32).ctypes.data_as(_i32p),
        np.ascontiguousarray(b.lengths, np.int32).ctypes.data_as(_i32p),
        _u8_ptr(np.ascontiguousarray(select, np.uint8)),
        _u8_ptr(np.ascontiguousarray(b.bases, np.uint8).reshape(-1)),
        _u8_ptr(np.ascontiguousarray(b.quals, np.uint8).reshape(-1)),
        ct.c_int64(b.lmax),
        _u8_ptr(names.buf), names.offsets.ctypes.data_as(_i64p),
        ct.c_int(1 if add_suffix else 0),
        ct.c_int64(n), _u8_ptr(out), ct.c_int64(cap), ct.c_int(_nthreads()),
    )
    if got < 0:
        return None
    return out[:got].tobytes()


def cigar_strings(cigar_ops, cigar_lens, cigar_n):
    """Columnar cigars -> (buf u8, offsets i64[N+1]) run-length strings
    ('*' when no ops); None if native unavailable."""
    lib = _lib()
    if lib is None:
        return None
    ops = np.ascontiguousarray(cigar_ops, np.uint8)
    lens = np.ascontiguousarray(cigar_lens, np.int32)
    n_ops = np.ascontiguousarray(cigar_n, np.int32)
    n, C = ops.shape if ops.ndim == 2 else (len(n_ops), 0)
    if C == 0:
        off = np.arange(n + 1, dtype=np.int64)
        return np.full(n, ord("*"), np.uint8), off
    cap = int(12 * int(np.minimum(n_ops, C).clip(0).sum()) + n + 64)
    out = _pretouch(np.empty(cap, np.uint8))
    offsets = np.empty(n + 1, np.int64)
    got = lib.cigar_strings(
        _u8_ptr(ops.reshape(-1)), lens.ctypes.data_as(_i32p),
        n_ops.ctypes.data_as(_i32p), ct.c_int64(n), ct.c_int64(C),
        _u8_ptr(out), ct.c_int64(cap), offsets.ctypes.data_as(_i64p),
        ct.c_int(_nthreads()),
    )
    if got < 0:
        return None
    return out[:got], offsets




def _spans_in_bounds(starts: np.ndarray, lens: np.ndarray, size: int) -> bool:
    """Corrupt-offset guard shared by the span gather wrappers: negative
    lens from non-monotonic offsets would otherwise overflow out buffers."""
    if not len(starts):
        return True
    return (
        int((starts + lens).max()) <= size
        and int(starts.min()) >= 0
        and int(lens.min()) >= 0
    )

def span_gather(src: np.ndarray, starts: np.ndarray, lens: np.ndarray,
                total: int):
    """Packed gather of byte spans [starts[i], starts[i]+lens[i]) ->
    u8[total]; None if native unavailable.  The StringColumn.take hot
    path."""
    lib = _lib()
    if lib is None:
        return None
    src = np.ascontiguousarray(src, np.uint8)
    starts = np.ascontiguousarray(starts, np.int64)
    lens = np.ascontiguousarray(lens, np.int64)
    if not _spans_in_bounds(starts, lens, src.size):
        return None  # corrupt offsets: numpy path's fail-safe instead
    out = np.empty(int(total), np.uint8)
    lib.span_gather(
        _u8_ptr(src), starts.ctypes.data_as(_i64p),
        lens.ctypes.data_as(_i64p), ct.c_int64(len(starts)), _u8_ptr(out),
    )
    return out


def lut_compact_rows(mat: np.ndarray, lens: np.ndarray, lut: np.ndarray):
    """Padded byte matrix [N, W] -> (LUT-mapped compact string buffer,
    i64 arrow offsets); None if native unavailable.

    One fused pass standing in for the numpy pair
    ``LUT[mat]`` + ``StringColumn.from_matrix`` that dominated the
    Parquet part encode (sequence/qual columns)."""
    lib = _lib()
    if lib is None:
        return None
    mat = np.ascontiguousarray(mat, np.uint8)
    n, w = mat.shape
    lens32 = np.clip(np.asarray(lens), 0, w).astype(np.int32)
    lut = np.ascontiguousarray(lut, np.uint8)
    if lut.size < 256:
        return None
    off = np.zeros(n + 1, np.int64)
    np.cumsum(lens32, out=off[1:])
    out = _pretouch(np.empty(max(1, int(off[-1])), np.uint8))
    lib.lut_compact_rows(
        _u8_ptr(mat.reshape(-1)), lens32.ctypes.data_as(_i32p),
        off.ctypes.data_as(_i64p), ct.c_int64(n), ct.c_int64(w),
        _u8_ptr(lut), _u8_ptr(out), _nthreads(),
    )
    return out[: int(off[-1])], off


def line_index_strided(data, begin: int, stride: int):
    """Byte offsets of every ``stride``-th line start in ``data[begin:]``
    plus the final end offset -> i64 array; None if native unavailable.

    The windowed SAM reader's replacement for a whole-buffer numpy
    newline scan."""
    lib = _lib()
    if lib is None:
        return None
    buf = _as_u8(data)
    n = len(buf)
    stride = max(1, int(stride))
    cap = (n - int(begin)) // stride + 3
    out = np.empty(cap, np.int64)
    got = lib.line_index_strided(
        _u8_ptr(buf), ct.c_int64(n), ct.c_int64(begin),
        ct.c_int64(stride), out.ctypes.data_as(_i64p), ct.c_int64(cap),
    )
    if got < 0:
        return None
    return out[:got]


def realign_prep(b, md_col_buf, md_col_off, md_valid, grows, goff,
                 gen_consensus: bool):
    """Native phase-1 realignment prep (see native/realign.cpp).

    ``b`` is a numpy ReadBatch view of the candidate rows; groups are the
    flat row list + offsets.  Returns a dict of per-target, per-to-clean-
    read and per-consensus arrays, or None when native is unavailable.
    Raises the same exception classes the Python path raises (ValueError
    for malformed MD / missing deleted bases, IndexError for CIGAR
    overruns)."""
    lib = _lib()
    if lib is None:
        return None
    bases = np.ascontiguousarray(b.bases, np.uint8)
    quals = np.ascontiguousarray(b.quals, np.uint8)
    N, L = bases.shape
    lengths = np.ascontiguousarray(b.lengths, np.int32)
    start = np.ascontiguousarray(b.start, np.int64)
    ops = np.ascontiguousarray(b.cigar_ops, np.uint8)
    lens = np.ascontiguousarray(b.cigar_lens, np.int32)
    n_ops = np.ascontiguousarray(b.cigar_n, np.int32)
    C = ops.shape[1]
    md_buf = np.ascontiguousarray(md_col_buf, np.uint8)
    md_off = np.ascontiguousarray(md_col_off, np.int64)
    md_val = np.ascontiguousarray(md_valid, np.uint8)
    grows = np.ascontiguousarray(grows, np.int64)
    goff = np.ascontiguousarray(goff, np.int64)
    G = len(goff) - 1
    h = lib.realign_prep(
        _u8_ptr(bases), _u8_ptr(quals), ct.c_int64(N), ct.c_int64(L),
        lengths.ctypes.data_as(_i32p), start.ctypes.data_as(_i64p),
        _u8_ptr(ops.reshape(-1)), lens.ctypes.data_as(_i32p),
        n_ops.ctypes.data_as(_i32p), ct.c_int64(C),
        _u8_ptr(md_buf), md_off.ctypes.data_as(_i64p), _u8_ptr(md_val),
        grows.ctypes.data_as(_i64p), goff.ctypes.data_as(_i64p),
        ct.c_int64(G), ct.c_int(1 if gen_consensus else 0),
    )
    if not h:
        return None
    try:
        dims = [np.zeros(1, np.int64) for _ in range(8)]
        lib.realign_prep_dims(
            ct.c_void_p(h), *[d.ctypes.data_as(_i64p) for d in dims]
        )
        (n_reads, cigar_bytes, md_bytes, n_cons, cons_bytes, ref_bytes,
         err, err_row) = (int(d[0]) for d in dims)
        if err:
            if err == 2:
                raise IndexError(
                    f"realign prep: CIGAR overruns read at row {err_row}"
                )
            raise ValueError(
                f"realign prep: malformed MD/alignment at row {err_row}"
            )
        out = {
            "t_status": np.zeros(G, np.int32),
            "t_ref_buf": np.zeros(max(ref_bytes, 1), np.uint8),
            "t_ref_off": np.zeros(G + 1, np.int64),
            "t_ref_start": np.zeros(G, np.int64),
            "t_ref_end": np.zeros(G, np.int64),
            "r_group": np.zeros(n_reads, np.int32),
            "r_row": np.zeros(n_reads, np.int64),
            "r_cigar_buf": np.zeros(max(cigar_bytes, 1), np.uint8),
            "r_cigar_off": np.zeros(n_reads + 1, np.int64),
            "r_md_buf": np.zeros(max(md_bytes, 1), np.uint8),
            "r_md_off": np.zeros(n_reads + 1, np.int64),
            "r_md_set": np.zeros(n_reads, np.uint8),
            "r_dirty": np.zeros(n_reads, np.uint8),
            "r_pure": np.zeros(n_reads, np.uint8),
            "r_orig_qual": np.zeros(n_reads, np.int64),
            "c_group": np.zeros(n_cons, np.int32),
            "c_seq_buf": np.zeros(max(cons_bytes, 1), np.uint8),
            "c_seq_off": np.zeros(n_cons + 1, np.int64),
            "c_is": np.zeros(n_cons, np.int64),
            "c_ie": np.zeros(n_cons, np.int64),
        }
        lib.realign_prep_fill(
            ct.c_void_p(h),
            out["t_status"].ctypes.data_as(_i32p),
            _u8_ptr(out["t_ref_buf"]),
            out["t_ref_off"].ctypes.data_as(_i64p),
            out["t_ref_start"].ctypes.data_as(_i64p),
            out["t_ref_end"].ctypes.data_as(_i64p),
            out["r_group"].ctypes.data_as(_i32p),
            out["r_row"].ctypes.data_as(_i64p),
            _u8_ptr(out["r_cigar_buf"]),
            out["r_cigar_off"].ctypes.data_as(_i64p),
            _u8_ptr(out["r_md_buf"]),
            out["r_md_off"].ctypes.data_as(_i64p),
            _u8_ptr(out["r_md_set"]),
            _u8_ptr(out["r_dirty"]),
            _u8_ptr(out["r_pure"]),
            out["r_orig_qual"].ctypes.data_as(_i64p),
            out["c_group"].ctypes.data_as(_i32p),
            _u8_ptr(out["c_seq_buf"]),
            out["c_seq_off"].ctypes.data_as(_i64p),
            out["c_is"].ctypes.data_as(_i64p),
            out["c_ie"].ctypes.data_as(_i64p),
        )
        return out
    finally:
        lib.realign_prep_free(ct.c_void_p(h))


def md_move_batch(b, rows, ref_buf, ref_off, tloc, offs,
                  head_len, mid_len, mid_op, end_len, new_start):
    """Batched MdTag.move_alignment + canonical to_string for realigned
    reads.  Returns (md_buf u8, md_off i64) or None when unavailable."""
    lib = _lib()
    if lib is None:
        return None
    bases = np.ascontiguousarray(b.bases, np.uint8)
    N, L = bases.shape
    lengths = np.ascontiguousarray(b.lengths, np.int32)
    rows = np.ascontiguousarray(rows, np.int64)
    K = len(rows)
    ref_buf = np.ascontiguousarray(ref_buf, np.uint8)
    ref_off = np.ascontiguousarray(ref_off, np.int64)
    tloc = np.ascontiguousarray(tloc, np.int32)
    offs = np.ascontiguousarray(offs, np.int64)
    head_len = np.ascontiguousarray(head_len, np.int32)
    mid_len = np.ascontiguousarray(mid_len, np.int32)
    mid_op = np.ascontiguousarray(mid_op, np.uint8)
    end_len = np.ascontiguousarray(end_len, np.int32)
    new_start = np.ascontiguousarray(new_start, np.int64)
    # MD length bound: digits+bases over the span plus deletion bases
    cap = int(K * (L + 64) + int(mid_len.sum()) + 64)
    err = np.zeros(1, np.int64)
    err_row = np.zeros(1, np.int64)
    for _ in range(2):
        out = np.zeros(max(cap, 1), np.uint8)
        out_off = np.zeros(K + 1, np.int64)
        got = lib.md_move_batch(
            _u8_ptr(bases), ct.c_int64(N), ct.c_int64(L),
            lengths.ctypes.data_as(_i32p),
            rows.ctypes.data_as(_i64p), ct.c_int64(K),
            _u8_ptr(ref_buf), ref_off.ctypes.data_as(_i64p),
            tloc.ctypes.data_as(_i32p), offs.ctypes.data_as(_i64p),
            head_len.ctypes.data_as(_i32p), mid_len.ctypes.data_as(_i32p),
            _u8_ptr(mid_op), end_len.ctypes.data_as(_i32p),
            new_start.ctypes.data_as(_i64p),
            _u8_ptr(out), ct.c_int64(cap), out_off.ctypes.data_as(_i64p),
            err.ctypes.data_as(_i64p), err_row.ctypes.data_as(_i64p),
        )
        if int(err[0]):
            if int(err[0]) == 2:
                raise IndexError(
                    f"md_move_batch: alignment overrun at row {int(err_row[0])}"
                )
            raise ValueError(
                f"md_move_batch: bad alignment at row {int(err_row[0])}"
            )
        if got >= 0:
            return out[:got], out_off
        cap = -got
    return None


def span_gather_strided(src: np.ndarray, starts: np.ndarray,
                        lens: np.ndarray, w: int):
    """Gather byte spans into a zero-padded [n, w] matrix (row-strided);
    None if native unavailable.  StringColumn.to_fixed_bytes hot path."""
    lib = _lib()
    if lib is None:
        return None
    src = np.ascontiguousarray(src, np.uint8)
    starts = np.ascontiguousarray(starts, np.int64)
    lens = np.ascontiguousarray(lens, np.int64)
    n = len(starts)
    if not _spans_in_bounds(starts, lens, src.size) or (
        n and int(lens.max()) > w
    ):
        return None  # corrupt offsets: preserve the numpy fail-safe
    out = np.zeros((n, int(w)), np.uint8)
    lib.span_gather_strided(
        _u8_ptr(src), starts.ctypes.data_as(_i64p),
        lens.ctypes.data_as(_i64p), ct.c_int64(n), ct.c_int64(int(w)),
        _u8_ptr(out),
    )
    return out
