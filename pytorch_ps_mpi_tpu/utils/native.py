"""ctypes bindings + lazy g++ build for the native wire codec.

The reference reached its native compressor through a third-party binding
(python-blosc → c-blosc, ``mpi_comms.py:25,29``); here the native code is
part of the framework (``native/wirecodec.cpp``) and compiled on first use
with the system toolchain. Pure-numpy fallbacks keep every feature working
when no compiler is available.

Wire format of :func:`compress` (little-endian):
  magic ``b'WC02'`` | u8 elem_size | u8 flags (1 = shuffled) | u64 raw_len
  | u32 crc32(raw) | payload (rle0, or stored raw when elem_size == 0)
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import struct
import subprocess
import sys
import tempfile
import zlib
from typing import Optional

import numpy as np

_MAGIC = b"WC02"
_HDR = struct.Struct("<4sBBQI")

_lib: Optional[ctypes.CDLL] = None
_BUILD_FAILURES: set = set()


class _FoldSpan(ctypes.Structure):
    """ctypes mirror of ``wirecodec.cpp``'s ``FoldSpan`` — one
    (start_ns, end_ns, elems) interval per ``wc_fold_*`` call, captured
    by the armed native span ring for the hop-anatomy plane. Layout is
    size-checked at load against ``wc_abi_fold_span_bytes`` and diffed
    field-for-field by the psanalyze ABI-drift rule."""

    _pack_ = 1
    _fields_ = [
        ("start_ns", ctypes.c_uint64),
        ("end_ns", ctypes.c_uint64),
        ("elems", ctypes.c_uint64),
    ]


assert ctypes.sizeof(_FoldSpan) == 24, "FoldSpan ctypes mirror drifted"

#: ``PS_NATIVE_SANITIZE`` → extra g++ flags. The sanitized builds land
#: in ``native/_build/<mode>/`` so they never clobber the normal cache;
#: ``make native-asan``/``native-ubsan`` (tools/native_sanitize.py) run
#: the parity suite against them with the runtime LD_PRELOADed (the
#: Python binary itself is uninstrumented). ``-ffp-contract=off`` stays:
#: the bit-exact native==numpy fold contract must hold under sanitizers
#: too, or the parity suite would be testing a different kernel.
SANITIZE_FLAGS = {
    "asan": ("-fsanitize=address", "-fno-omit-frame-pointer", "-g", "-O1"),
    "ubsan": ("-fsanitize=undefined", "-fno-sanitize-recover=all",
              "-g", "-O1"),
    "tsan": ("-fsanitize=thread", "-g", "-O1"),
}


def sanitize_mode() -> Optional[str]:
    """The active ``PS_NATIVE_SANITIZE`` mode, or None. Unknown values
    raise at the first build rather than silently producing an
    unsanitized library that a leak-check run would then vouch for."""
    mode = os.environ.get("PS_NATIVE_SANITIZE", "").strip().lower()
    if not mode:
        return None
    if mode not in SANITIZE_FLAGS:
        raise ValueError(
            f"PS_NATIVE_SANITIZE={mode!r}: expected one of "
            f"{sorted(SANITIZE_FLAGS)}")
    return mode


def _repo_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def build_and_load(src_name: str, extra_flags=()) -> Optional[ctypes.CDLL]:
    """Compile ``native/<src_name>`` with g++ into ``native/_build`` and
    dlopen it. The build is keyed on content: a hash of the source and
    the compile command is recorded beside the ``.so``, and the library
    is rebuilt whenever that hash differs or is missing — file times say
    nothing (a copy or a checkout does not preserve them). Returns None
    — latched, and said once on stderr — if the source is missing or the
    toolchain fails, so callers fall back to pure Python. Shared by
    every native component (wirecodec, psqueue, tcpps).

    With ``PS_NATIVE_SANITIZE=asan|ubsan|tsan`` the library is built
    with the matching sanitizer into a mode-specific cache directory."""
    mode = sanitize_mode()
    if (src_name, mode) in _BUILD_FAILURES:
        return None
    src = os.path.join(_repo_root(), "native", src_name)
    stem = os.path.splitext(src_name)[0]
    build_dir = os.path.join(_repo_root(), "native", "_build",
                             *([mode] if mode else []))
    so_path = os.path.join(build_dir, f"lib{stem}.so")
    key_path = so_path + ".key"
    if mode:
        extra_flags = (*extra_flags, *SANITIZE_FLAGS[mode])
    # -lrt AFTER the source (link order): shm_open lives in librt on
    # pre-2.34 glibc; newer glibc ships a no-op librt. Linux only —
    # other platforms have no librt and the flag would fail the build
    libs = ["-lrt"] if sys.platform.startswith("linux") else []
    # -ffp-contract=off: the wc_fold_* kernels must not contract
    # multiply+add into an FMA — the numpy fallback computes them as
    # separate f32 ops and the native==numpy bit-exact parity contract
    # (tests/test_native_fold.py) pins that
    flags = ["g++", "-O3", "-std=c++17", "-ffp-contract=off",
             "-shared", "-fPIC", *extra_flags]
    try:
        with open(src, "rb") as f:
            key = hashlib.sha256(
                f.read() + "\0".join(flags + libs).encode()).hexdigest()
        os.makedirs(build_dir, exist_ok=True)
        if not os.path.exists(so_path) or _read_text(key_path) != key:
            tmp = tempfile.mktemp(suffix=".so", dir=build_dir)
            cmd = [*flags, "-o", tmp, src, *libs]
            # scrubbed env: under `make native-asan` the PYTHON process
            # runs with the ASan runtime LD_PRELOADed and leak-checking
            # armed — inherited into g++ that flags the compiler's own
            # exit-time allocations and fails the build
            env = {k: v for k, v in os.environ.items()
                   if k not in ("LD_PRELOAD", "ASAN_OPTIONS",
                                "LSAN_OPTIONS", "UBSAN_OPTIONS",
                                "TSAN_OPTIONS")}
            subprocess.run(cmd, check=True, capture_output=True,
                           timeout=120, env=env)
            os.replace(tmp, so_path)
            # the key lands after the library: a crash between the two
            # leaves a mismatch, which rebuilds
            with open(tmp + ".key", "w") as f:
                f.write(key)
            os.replace(tmp + ".key", key_path)
        return ctypes.CDLL(so_path)
    except Exception as e:
        _BUILD_FAILURES.add((src_name, mode))
        # g++'s own words, when it was g++ that failed
        said = (getattr(e, "stderr", None) or b"").decode(errors="replace")
        print(f"native: lib{stem}.so unavailable ({type(e).__name__}: {e}) "
              f"{said[-400:].strip()}\nnative: the pure-Python path takes "
              "over", file=sys.stderr, flush=True)
        return None


def _read_text(path: str) -> Optional[str]:
    try:
        with open(path) as f:
            return f.read()
    except OSError:
        return None


def _build_lib() -> Optional[ctypes.CDLL]:
    lib = build_and_load("wirecodec.cpp")
    if lib is None:
        return None
    u8p = ctypes.POINTER(ctypes.c_uint8)
    lib.wc_shuffle.argtypes = [u8p, u8p, ctypes.c_size_t, ctypes.c_size_t]
    lib.wc_unshuffle.argtypes = [u8p, u8p, ctypes.c_size_t, ctypes.c_size_t]
    lib.wc_rle0_max_out.argtypes = [ctypes.c_size_t]
    lib.wc_rle0_max_out.restype = ctypes.c_size_t
    lib.wc_rle0_encode.argtypes = [u8p, ctypes.c_size_t, u8p, ctypes.c_size_t]
    lib.wc_rle0_encode.restype = ctypes.c_size_t
    lib.wc_rle0_decode.argtypes = [u8p, ctypes.c_size_t, u8p, ctypes.c_size_t]
    lib.wc_rle0_decode.restype = ctypes.c_size_t
    # fold kernels (absent from a stale cached .so built before they
    # existed — probe one symbol and leave the rest unbound then; the
    # content key above rebuilds on any source change, so this only
    # guards a hand-copied old library)
    try:
        f32p = ctypes.POINTER(ctypes.c_float)
        i8p = ctypes.POINTER(ctypes.c_int8)
        i32p = ctypes.POINTER(ctypes.c_int32)
        u16p = ctypes.POINTER(ctypes.c_uint16)
        lib.wc_fold_scaled_i8.argtypes = [f32p, i8p, ctypes.c_float,
                                          ctypes.c_size_t]
        lib.wc_fold_tern.argtypes = [f32p, u8p, ctypes.c_float,
                                     ctypes.c_size_t]
        lib.wc_fold_sign.argtypes = [i32p, u8p, ctypes.c_size_t]
        lib.wc_fold_sparse.argtypes = [f32p, f32p, i32p, ctypes.c_size_t,
                                       ctypes.c_size_t]
        lib.wc_zero_sparse.argtypes = [f32p, i32p, ctypes.c_size_t,
                                       ctypes.c_size_t]
        lib.wc_fold_sparse_q8.argtypes = [f32p, i8p, f32p, i32p,
                                          ctypes.c_size_t, ctypes.c_size_t,
                                          ctypes.c_size_t]
        lib.wc_fold_dense_f32.argtypes = [f32p, f32p, ctypes.c_size_t]
        lib.wc_fold_dense_bf16.argtypes = [f32p, u16p, ctypes.c_size_t]
        lib._has_folds = True
    except AttributeError:
        lib._has_folds = False
    # fold-span capture ring (hop anatomy) — own probe so a stale .so
    # built with folds but before the ring degrades only the ring
    try:
        u64p = ctypes.POINTER(ctypes.c_uint64)
        lib.wc_abi_fold_span_bytes.argtypes = []
        lib.wc_abi_fold_span_bytes.restype = ctypes.c_uint32
        lib.wc_fold_spans_arm.argtypes = [ctypes.c_uint32]
        lib.wc_fold_spans_arm.restype = ctypes.c_int
        lib.wc_fold_spans_drain.argtypes = [ctypes.POINTER(_FoldSpan),
                                            ctypes.c_uint32, u64p]
        lib.wc_fold_spans_drain.restype = ctypes.c_uint32
        # load-time ABI twin: the native struct size must equal the
        # ctypes mirror's before ANY drain call is allowed
        if int(lib.wc_abi_fold_span_bytes()) != ctypes.sizeof(_FoldSpan):
            raise RuntimeError(
                "FoldSpan ABI drift: wirecodec.cpp packs "
                f"{int(lib.wc_abi_fold_span_bytes())} bytes, the ctypes "
                f"mirror {ctypes.sizeof(_FoldSpan)}")
        lib._has_fold_spans = True
    except AttributeError:
        lib._has_fold_spans = False
    return lib


def get_lib() -> Optional[ctypes.CDLL]:
    """The loaded native library, building it on first call; None if the
    toolchain is unavailable (numpy fallbacks take over)."""
    global _lib
    if _lib is None:
        _lib = _build_lib()
    return _lib


def fold_profile_stats() -> Optional[dict]:
    """The wc_fold_* cycle counters (calls / elements / wall ns) — the
    native half of continuous profiling (telemetry.profiler). Reads the
    ALREADY-loaded library only (never triggers a build: a process that
    armed no folds reports nothing, not zeros); None when unavailable
    or built before the counters existed."""
    lib = _lib
    if lib is None or not getattr(lib, "_has_folds", False):
        return None
    if not hasattr(lib, "wc_profile_stats"):
        return None
    calls = ctypes.c_uint64()
    elems = ctypes.c_uint64()
    ns = ctypes.c_uint64()
    lib.wc_profile_stats(ctypes.byref(calls), ctypes.byref(elems),
                         ctypes.byref(ns))
    return {"fold_calls": int(calls.value),
            "fold_elems": int(elems.value),
            "fold_ns": int(ns.value)}


def fold_spans_arm(capacity: int) -> bool:
    """Arm (capacity > 0) or disarm (0) the native per-fold-call span
    ring the hop-anatomy plane drains. Returns True when the ring is
    live. Honors ``PS_NO_NATIVE`` (the Python fallback times folds
    itself); call only from the fold-running thread."""
    if fast_path_disabled():
        return False
    lib = get_lib()
    if lib is None or not getattr(lib, "_has_fold_spans", False):
        return False
    return int(lib.wc_fold_spans_arm(int(capacity))) == 0


def fold_spans_drain(max_spans: int = 4096
                     ) -> Optional[tuple]:
    """Drain the armed span ring: ``([(start_ns, end_ns, elems), ...],
    dropped_count)`` — oldest first, drop counter reset per drain — or
    None when the ring is unavailable. Reads the ALREADY-loaded library
    only, from the fold-running thread (same affinity discipline as
    ``tps_server_read_stats``)."""
    lib = _lib
    if lib is None or not getattr(lib, "_has_fold_spans", False):
        return None
    buf = (_FoldSpan * int(max_spans))()
    dropped = ctypes.c_uint64()
    n = int(lib.wc_fold_spans_drain(buf, int(max_spans),
                                    ctypes.byref(dropped)))
    spans = [(int(buf[i].start_ns), int(buf[i].end_ns), int(buf[i].elems))
             for i in range(n)]
    return spans, int(dropped.value)


def _u8(arr: np.ndarray):
    return arr.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))


# -- filters (native with numpy fallback) -----------------------------------

def shuffle(data: np.ndarray, elem_size: int) -> np.ndarray:
    data = np.ascontiguousarray(data, dtype=np.uint8)
    if elem_size <= 0 or data.size % elem_size != 0:
        raise ValueError(f"size {data.size} not divisible by elem_size {elem_size}")
    n = data.size // elem_size
    lib = get_lib()
    if lib is not None:
        out = np.empty_like(data)
        lib.wc_shuffle(_u8(data), _u8(out), n, elem_size)
        return out
    return data.reshape(n, elem_size).T.reshape(-1).copy()


def unshuffle(data: np.ndarray, elem_size: int) -> np.ndarray:
    data = np.ascontiguousarray(data, dtype=np.uint8)
    if elem_size <= 0 or data.size % elem_size != 0:
        raise ValueError(f"size {data.size} not divisible by elem_size {elem_size}")
    n = data.size // elem_size
    lib = get_lib()
    if lib is not None:
        out = np.empty_like(data)
        lib.wc_unshuffle(_u8(data), _u8(out), n, elem_size)
        return out
    return data.reshape(elem_size, n).T.reshape(-1).copy()


def _rle0_encode_np(src: np.ndarray) -> bytes:
    """Numpy fallback of the C encoder (identical format)."""
    out = bytearray()

    def put_varint(v: int):
        while v >= 0x80:
            out.append((v & 0x7F) | 0x80)
            v >>= 7
        out.append(v)

    n = src.size
    i = 0
    is_zero = src == 0
    while i < n:
        zrun = 0
        while i + zrun < n and is_zero[i + zrun]:
            zrun += 1
        lit_start = i + zrun
        lit = 0
        while lit_start + lit < n:
            if is_zero[lit_start + lit]:
                z = 0
                while lit_start + lit + z < n and is_zero[lit_start + lit + z]:
                    z += 1
                if z >= 2:
                    break
            lit += 1
        put_varint(zrun)
        put_varint(lit)
        out += src[lit_start : lit_start + lit].tobytes()
        i = lit_start + lit
    return bytes(out)


def _rle0_decode_np(src: bytes, raw_len: int) -> np.ndarray:
    out = np.empty(raw_len, np.uint8)
    i = 0
    o = 0
    n = len(src)

    def get_varint(i):
        v = 0
        shift = 0
        while True:
            b = src[i]
            v |= (b & 0x7F) << shift
            i += 1
            if not (b & 0x80):
                return v, i
            shift += 7

    while i < n:
        zrun, i = get_varint(i)
        lit, i = get_varint(i)
        out[o : o + zrun] = 0
        o += zrun
        out[o : o + lit] = np.frombuffer(src, np.uint8, lit, i)
        o += lit
        i += lit
    if o != raw_len:
        raise ValueError(f"corrupt rle0 stream: got {o}, want {raw_len}")
    return out


def rle0_encode(data: np.ndarray) -> bytes:
    data = np.ascontiguousarray(data, dtype=np.uint8)
    lib = get_lib()
    if lib is not None:
        cap = lib.wc_rle0_max_out(data.size)
        out = np.empty(cap, np.uint8)
        size = lib.wc_rle0_encode(_u8(data), data.size, _u8(out), cap)
        if size == 0 and data.size > 0:
            raise RuntimeError("rle0 encode capacity overflow")
        return out[:size].tobytes()
    return _rle0_encode_np(data)


def rle0_decode(data: bytes, raw_len: int) -> np.ndarray:
    lib = get_lib()
    if lib is not None:
        src = np.frombuffer(data, np.uint8)
        out = np.empty(raw_len, np.uint8)
        size = lib.wc_rle0_decode(_u8(src), src.size, _u8(out), raw_len)
        if size != raw_len:
            raise ValueError(f"corrupt rle0 stream: got {size}, want {raw_len}")
        return out
    return _rle0_decode_np(data, raw_len)


# -- public compress/decompress (the reference's blosc surface) --------------

def compress(data: bytes, elem_size: int = 4) -> bytes:
    """Shuffle + RLE0 with a CRC32 of the raw bytes. Never expands by more
    than the 18-byte header; if the encoded form would be larger than raw,
    stores raw (elem_size=0 means stored)."""
    raw = np.frombuffer(data, np.uint8)
    crc = zlib.crc32(data) & 0xFFFFFFFF
    if raw.size % max(elem_size, 1) == 0 and elem_size > 1:
        payload = rle0_encode(shuffle(raw, elem_size))
        flags = 1
    else:
        payload = rle0_encode(raw)
        flags = 0
        elem_size = 1
    if len(payload) >= raw.size:  # incompressible: store
        return _HDR.pack(_MAGIC, 0, 0, raw.size, crc) + data
    return _HDR.pack(_MAGIC, elem_size, flags, raw.size, crc) + payload


# -- native fast path (fold kernels + batched ingest) ------------------------
#
# PS_NO_NATIVE=1 force-disables the OPTIONAL native fast paths — the
# wc_fold_* homomorphic fold kernels below and the tcpps batched C++
# frame ingest — proving the pure-Python/numpy fallbacks still carry
# every feature. It does NOT disable the native transports themselves
# (psqueue/tcpps ARE the shm/TCP wire; there is no Python substitute),
# nor the shuffle/rle0 filters above (their numpy fallbacks engage only
# when the toolchain is missing).

def fast_path_disabled() -> bool:
    """True when the ``PS_NO_NATIVE`` env var asks for pure-Python
    fallbacks (any value except empty/``0``/``false``). Read per call:
    tests flip it with monkeypatch."""
    return os.environ.get("PS_NO_NATIVE", "0").strip().lower() not in (
        "", "0", "false")


def fold_lib() -> Optional[ctypes.CDLL]:
    """The wirecodec library with the ``wc_fold_*`` kernels bound, or
    None (``PS_NO_NATIVE`` set, no toolchain, or a stale pre-fold
    cached build) — callers fall back to the numpy fold."""
    if fast_path_disabled():
        return None
    lib = get_lib()
    if lib is None or not getattr(lib, "_has_folds", False):
        return None
    return lib


def _f32(arr: np.ndarray):
    return arr.ctypes.data_as(ctypes.POINTER(ctypes.c_float))


def _i8(arr: np.ndarray):
    return arr.ctypes.data_as(ctypes.POINTER(ctypes.c_int8))


def _i32(arr: np.ndarray):
    return arr.ctypes.data_as(ctypes.POINTER(ctypes.c_int32))


def fold_scaled_i8(lib, acc: np.ndarray, q: np.ndarray, scale) -> None:
    """acc += scale * q (int8 payload, f32 accumulator) in one pass."""
    lib.wc_fold_scaled_i8(_f32(acc), _i8(q), ctypes.c_float(float(scale)),
                          acc.size)


def fold_tern(lib, acc: np.ndarray, packed: np.ndarray, scale) -> None:
    """acc += scale * unpack_base4(packed) (terngrad) in one pass."""
    lib.wc_fold_tern(_f32(acc), _u8(packed), ctypes.c_float(float(scale)),
                     acc.size)


def fold_sign(lib, votes: np.ndarray, packed: np.ndarray) -> None:
    """votes += unpacked bits (little bitorder), int32 vote counters."""
    lib.wc_fold_sign(_i32(votes), _u8(packed), votes.size)


def fold_sparse(lib, acc: np.ndarray, values: np.ndarray,
                indices: np.ndarray, acc_ptr=None) -> None:
    """acc[idx] += val scatter-add; out-of-range indices dropped.
    ``acc_ptr`` lets a hot caller reuse a cached ctypes pointer for the
    long-lived accumulator (the data_as conversion is ~µs — real money
    against a 2048-entry scatter)."""
    lib.wc_fold_sparse(acc_ptr if acc_ptr is not None else _f32(acc),
                       _f32(values), _i32(indices),
                       values.size, acc.size)


def zero_sparse(lib, acc: np.ndarray, indices: np.ndarray,
                acc_ptr=None) -> None:
    """acc[idx] = 0 for in-range idx — the pooled-buffer recycle pass."""
    lib.wc_zero_sparse(acc_ptr if acc_ptr is not None else _f32(acc),
                       _i32(indices), indices.size, acc.size)


def fold_sparse_q8(lib, acc: np.ndarray, q: np.ndarray, scales: np.ndarray,
                   indices: np.ndarray, acc_ptr=None) -> None:
    """Dequantized (per-block int8 x scale) scatter-add in one pass."""
    nb = scales.size
    kb = q.size // max(nb, 1)
    lib.wc_fold_sparse_q8(acc_ptr if acc_ptr is not None else _f32(acc),
                          _i8(q), _f32(scales), _i32(indices),
                          nb, kb, acc.size)


def fold_dense_f32(lib, acc: np.ndarray, x: np.ndarray) -> None:
    lib.wc_fold_dense_f32(_f32(acc), _f32(x), acc.size)


def fold_dense_bf16(lib, acc: np.ndarray, x: np.ndarray) -> None:
    lib.wc_fold_dense_bf16(
        _f32(acc), x.ctypes.data_as(ctypes.POINTER(ctypes.c_uint16)),
        acc.size)


def decompress(blob: bytes) -> bytes:
    magic, elem_size, flags, raw_len, crc = _HDR.unpack_from(blob, 0)
    if magic != _MAGIC:
        raise ValueError("not a wirecodec blob")
    payload = blob[_HDR.size :]
    if elem_size == 0:  # stored
        out_bytes = payload[:raw_len]
    else:
        out = rle0_decode(payload, raw_len)
        if flags & 1:
            out = unshuffle(out, elem_size)
        out_bytes = out.tobytes()
    if (zlib.crc32(out_bytes) & 0xFFFFFFFF) != crc:
        raise ValueError("wirecodec blob failed CRC32 check (corrupt)")
    return out_bytes
