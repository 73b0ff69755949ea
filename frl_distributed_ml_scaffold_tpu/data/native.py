"""ctypes bindings for the native data-loader core (SURVEY C16).

Builds ``native/frl_data.cpp`` with g++ on first use into a binary whose
name carries the host's microarchitecture and a hash of the SOURCE'S
CONTENT, so a copy of the tree (which keeps no mtimes) and an edited
source each find — or build — exactly their own library; the binary is
never committed. Every entry point has a
pure-numpy fallback with identical semantics, so environments without a
toolchain degrade gracefully — ``native_available()`` reports which path is
live, and the parity tests assert C++ == numpy bit-for-bit where the
contract is exact (gather) and distributionally where it involves RNG.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import platform
import subprocess
import tempfile
import threading

import numpy as np

from frl_distributed_ml_scaffold_tpu.utils.logging import get_logger

_NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(__file__)), "native")
_SRC = os.path.join(_NATIVE_DIR, "frl_data.cpp")


def _cache_key() -> str | None:
    """Tag for the cached .so filename: machine arch + a hash of the CPU
    feature flags + a hash of the source text; None without the source.

    The library is built with ``-march=native`` and cached next to the
    source; on a shared filesystem a multi-host launch could otherwise load
    a lib built for a different CPU and die with SIGILL, so each distinct
    microarchitecture builds (and loads) its own copy. The source hash
    makes a stale binary impossible to load: an edited source has another
    name, and an mtime (which a copy of the tree does not preserve) is
    never consulted.
    """
    try:
        with open(_SRC, "rb") as fh:
            src = hashlib.sha256(fh.read()).hexdigest()[:12]
    except OSError:
        return None
    flags = ""
    try:
        with open("/proc/cpuinfo") as fh:
            for line in fh:
                if line.lower().startswith(("flags", "features")):
                    flags = line
                    break
    except OSError:
        pass
    h = hashlib.sha256(flags.encode()).hexdigest()[:8]
    return f"{platform.machine()}-{h}.{src}"


_lock = threading.Lock()
_lib: ctypes.CDLL | None = None
_tried = False
# Set once the claiming loader has published its result (success or
# fallback) — racing callers park on this OUTSIDE the lock.
_done = threading.Event()


def _build(lib_path: str) -> bool:
    # Compile to a unique temp path and rename into place: rename is
    # atomic on POSIX, so concurrent first-use builds (multi-process launch,
    # pytest workers, shared filesystem) can never load a torn .so. The
    # temp file is removed on every exit path.
    fd, tmp = tempfile.mkstemp(prefix="build-", suffix=".so", dir=_NATIVE_DIR)
    os.close(fd)
    cmd = [
        "g++", "-O3", "-march=native", "-std=c++17", "-shared", "-fPIC",
        "-pthread", _SRC, "-o", tmp,
    ]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.replace(tmp, lib_path)
        return True
    except (subprocess.SubprocessError, FileNotFoundError, OSError) as e:
        get_logger().warning(
            "native data core build failed (%s); using numpy fallback", e
        )
        return False
    finally:
        try:
            os.unlink(tmp)
        except OSError:
            pass


def _load() -> ctypes.CDLL | None:
    global _lib, _tried
    # _lock only claims/publishes; the g++ build (subprocess.run, up to
    # 120 s) and the dlopen run LOCK-FREE.  Holding the module lock
    # across them was graft-lint concurrency finding blocking-under-lock
    # (data/native.py _load -> _build -> subprocess.run): every data
    # thread's first native call would queue behind one compile.
    # Concurrent builds are already safe without the lock — _build
    # compiles to a unique temp path and os.replace is atomic.
    with _lock:
        claimed = not _tried
        _tried = True
    if not claimed:
        _done.wait()
        return _lib
    try:
        lib = _load_uncached()
        with _lock:
            _lib = lib
        return lib
    finally:
        _done.set()


def _load_uncached() -> ctypes.CDLL | None:
    """Build/bind the library (no caching, no locks held)."""
    if os.environ.get("FRL_TPU_NO_NATIVE"):
        return None
    key = _cache_key()
    if key is None:
        get_logger().warning(
            "native data core source %s not found; using numpy fallback",
            _SRC,
        )
        return None
    lib_path = os.path.join(_NATIVE_DIR, f"libfrl_data.{key}.so")
    if not os.path.exists(lib_path) and not _build(lib_path):
        return None
    try:
        lib = ctypes.CDLL(lib_path)
    except OSError as e:
        get_logger().warning("native data core load failed (%s)", e)
        return None
    try:
        lib.frl_version.restype = ctypes.c_int
        version = lib.frl_version()
        f64 = ctypes.POINTER(ctypes.c_float)
        i64 = ctypes.POINTER(ctypes.c_int64)
        u8 = ctypes.POINTER(ctypes.c_uint8)
        lib.frl_gather_rows.argtypes = [f64, i64, f64, ctypes.c_int64,
                                        ctypes.c_int64]
        lib.frl_gather_rows_u8.argtypes = [u8, i64, f64, ctypes.c_int64,
                                           ctypes.c_int64]
        lib.frl_augment_batch.argtypes = [
            f64, f64, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_int64, ctypes.c_int64, ctypes.c_uint64, ctypes.c_int,
            f64, f64,
        ]
        i32 = ctypes.POINTER(ctypes.c_int32)
        u16 = ctypes.POINTER(ctypes.c_uint16)
        u32 = ctypes.POINTER(ctypes.c_uint32)
        lib.frl_gather_windows_u16.argtypes = [
            u16, i64, i32, ctypes.c_int64, ctypes.c_int64
        ]
        lib.frl_gather_windows_u32.argtypes = [
            u32, i64, i32, ctypes.c_int64, ctypes.c_int64
        ]
    except AttributeError as e:
        get_logger().warning(
            "native data core missing symbols (%s); using numpy fallback",
            e,
        )
        return None
    get_logger().info("native data core loaded (v%d)", version)
    return lib


def native_available() -> bool:
    return _load() is not None


def _fptr(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_float))


def gather_rows(src: np.ndarray, idx: np.ndarray) -> np.ndarray:
    """dst[i] = src[idx[i]] as float32 (row = trailing dims).

    ``src`` is typically an ``np.load(mmap_mode="r")`` shard, used zero-copy
    (an ``ascontiguousarray`` here would fault the entire mmap into RAM);
    the parallel per-row copy is where the page faults happen, across the
    worker pool. float32 rows are memcpy'd; uint8 rows convert + scale to
    [0, 1] in the same pass. Other dtypes take the numpy fallback.
    """
    idx = np.ascontiguousarray(idx, dtype=np.int64)
    # Validated here so both code paths fail identically: the native kernel
    # would memcpy out of bounds where numpy raises (or, worse, silently
    # wraps negatives) — reject both, before either path runs.
    if idx.size and (idx.min() < 0 or idx.max() >= len(src)):
        bad = idx[(idx < 0) | (idx >= len(src))][0]
        raise IndexError(
            f"gather_rows index {bad} out of bounds for {len(src)} rows"
        )
    lib = _load()
    u8 = src.dtype == np.uint8
    if lib is None or not src.flags["C_CONTIGUOUS"] or (
        src.dtype != np.float32 and not u8
    ):
        out = np.ascontiguousarray(src[idx], dtype=np.float32)
        return out / np.float32(255.0) if u8 else out
    out = np.empty((len(idx),) + src.shape[1:], np.float32)
    row = int(np.prod(src.shape[1:], dtype=np.int64))
    iptr = idx.ctypes.data_as(ctypes.POINTER(ctypes.c_int64))
    if u8:
        # uint8 shards convert + scale to [0,1] in the gather pass itself.
        lib.frl_gather_rows_u8(
            src.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)), iptr,
            _fptr(out), len(idx), row,
        )
    else:
        lib.frl_gather_rows(_fptr(src), iptr, _fptr(out), len(idx), row)
    return out


def gather_windows(src: np.ndarray, starts: np.ndarray, window: int) -> np.ndarray:
    """dst[i] = src[starts[i] : starts[i] + window] as int32.

    The LM token-bin read path: ``src`` is a 1-D uint16/uint32 memmap;
    windows start at arbitrary offsets (plain row-gather can't express
    this). Native path parallelizes the page-faulting copies; the numpy
    fallback is bit-identical.
    """
    starts = np.ascontiguousarray(starts, dtype=np.int64)
    if starts.size and (
        starts.min() < 0 or starts.max() + window > len(src)
    ):
        bad = starts[(starts < 0) | (starts + window > len(src))][0]
        raise IndexError(
            f"gather_windows start {bad} (+{window}) out of bounds for "
            f"{len(src)} tokens"
        )
    lib = _load()
    fname = {
        np.dtype(np.uint16): "frl_gather_windows_u16",
        np.dtype(np.uint32): "frl_gather_windows_u32",
    }.get(src.dtype)
    if lib is None or fname is None or not src.flags["C_CONTIGUOUS"]:
        out = np.empty((len(starts), window), np.int32)
        for i, s in enumerate(starts):
            out[i] = src[s : s + window]
        return out
    out = np.empty((len(starts), window), np.int32)
    ptr_t = ctypes.c_uint16 if src.dtype == np.uint16 else ctypes.c_uint32
    getattr(lib, fname)(
        src.ctypes.data_as(ctypes.POINTER(ptr_t)),
        starts.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        len(starts),
        window,
    )
    return out


_IMAGENET_MEAN = np.array([0.485, 0.456, 0.406], np.float32)
_IMAGENET_STD = np.array([0.229, 0.224, 0.225], np.float32)


def augment_batch(
    x: np.ndarray,
    crop: int,
    *,
    seed: int,
    train: bool,
    mean: np.ndarray = _IMAGENET_MEAN,
    std: np.ndarray = _IMAGENET_STD,
) -> np.ndarray:
    """NHWC random-crop(+flip)+normalize (train) / center-crop (eval)."""
    n, h, w, c = x.shape
    if crop > h or crop > w:
        # Validated here so both code paths fail identically — the native
        # kernel would otherwise read out of bounds where numpy raises.
        raise ValueError(f"crop {crop} exceeds stored image size {h}x{w}")
    mean = np.ascontiguousarray(np.broadcast_to(mean, (c,)), np.float32)
    std = np.ascontiguousarray(np.broadcast_to(std, (c,)), np.float32)
    lib = _load()
    if lib is None:
        return _augment_numpy(x, crop, seed=seed, train=train, mean=mean, std=std)
    x = np.ascontiguousarray(x, np.float32)
    out = np.empty((n, crop, crop, c), np.float32)
    lib.frl_augment_batch(
        _fptr(x), _fptr(out), n, h, w, c, crop,
        ctypes.c_uint64(seed & (2**64 - 1)), int(train), _fptr(mean),
        _fptr(std),
    )
    return out


_M64 = (1 << 64) - 1


def _splitmix64(s: int) -> tuple[int, int]:
    """One splitmix64 step — bit-identical to the C++ kernel's RNG."""
    s = (s + 0x9E3779B97F4A7C15) & _M64
    z = s
    z = ((z ^ (z >> 30)) * 0xBF58476D1CE4E5B9) & _M64
    z = ((z ^ (z >> 27)) * 0x94D049BB133111EB) & _M64
    return s, (z ^ (z >> 31)) & _M64


def _uniform01(s: int) -> tuple[int, np.float32]:
    s, z = _splitmix64(s)
    return s, np.float32(z >> 40) * np.float32(1.0 / 16777216.0)


def _augment_numpy(x, crop, *, seed, train, mean, std):
    """Numpy fallback with the SAME splitmix64 draws as the C++ kernel.

    Identical RNG streams matter: batches are pure functions of
    (seed, step) per the resume contract, so resuming in an environment
    whose native availability differs must not change the training stream.
    The parity test asserts native == numpy bit-for-bit.
    """
    n, h, w, c = x.shape
    out = np.empty((n, crop, crop, c), np.float32)
    max_y, max_x = h - crop, w - crop
    for i in range(n):
        if train:
            # Same per-sample stream derivation and draw order as C++
            # (draws skipped when the crop has no freedom, as there).
            s = (seed ^ ((0x243F6A8885A308D3 * (i + 1)) & _M64)) & _M64
            y0 = x0 = 0
            if max_y > 0:
                s, u = _uniform01(s)
                y0 = min(int(np.float32(u * np.float32(max_y + 1))), max_y)
            if max_x > 0:
                s, u = _uniform01(s)
                x0 = min(int(np.float32(u * np.float32(max_x + 1))), max_x)
            s, u = _uniform01(s)
            patch = x[i, y0:y0 + crop, x0:x0 + crop]
            if u < np.float32(0.5):
                patch = patch[:, ::-1]
        else:
            y0, x0 = max_y // 2, max_x // 2
            patch = x[i, y0:y0 + crop, x0:x0 + crop]
        out[i] = (np.asarray(patch, np.float32) - mean) / std
    return out


