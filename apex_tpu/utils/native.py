"""Loader for the native host runtime (csrc/flat_runtime.cpp).

Builds the shared library on demand with g++ (the image has no pybind11;
the C ABI + ctypes is the binding layer) and exposes numpy-level wrappers.
Everything degrades to numpy fallbacks when the toolchain is unavailable —
the same graceful-degradation stance as the rest of the framework (the
reference instead *raises* when its extensions are missing,
apex/multi_tensor_apply/multi_tensor_apply.py:20-22).
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from typing import Optional, Sequence

import numpy as np

_CSRC = os.path.join(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))), "csrc")
_SRCS = [os.path.join(_CSRC, "flat_runtime.cpp"),
         os.path.join(_CSRC, "image_pipeline.cpp")]
_BUILD_DIR = os.path.join(_CSRC, "_build")


def _lib_name() -> str:
    """The library's name carries the content hash of the sources it
    was built from, so a ``.so`` that is loaded provably matches
    ``csrc/*.cpp`` — a stale build left in an (ignored) ``_build/``, or
    in the per-user temp dir by another checkout, has another name and
    is simply never found."""
    h = hashlib.sha256()
    for src in _SRCS:
        with open(src, "rb") as f:
            h.update(f.read())
    return f"libapex_tpu_runtime-{h.hexdigest()[:16]}.so"


def _tmp_build_dir() -> str:
    import tempfile
    return os.path.join(tempfile.gettempdir(),
                        f"apex_tpu_build_{os.getuid()}")


def _dir_is_safe(d: str) -> bool:
    """Only trust a build dir we own that nobody else can write to —
    loading a .so from a predictable world-writable path is code
    injection on shared machines."""
    try:
        st = os.stat(d)
    except OSError:
        return False
    return st.st_uid == os.getuid() and not (st.st_mode & 0o022)

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_tried = False


def _build(name: str) -> Optional[str]:
    # Build next to the source when the install is writable; otherwise
    # (read-only site-packages) fall back to a per-user 0700 temp dir.
    for build_dir in (_BUILD_DIR, _tmp_build_dir()):
        try:
            os.makedirs(build_dir, mode=0o700, exist_ok=True)
        except OSError:
            continue
        if build_dir != _BUILD_DIR and not _dir_is_safe(build_dir):
            continue  # pre-existing dir owned by someone else
        lib = os.path.join(build_dir, name)
        # build under a private name, then rename: another process never
        # opens a half-written library
        tmp = f"{lib}.{os.getpid()}.tmp"
        cmd = ["g++", "-O3", "-shared", "-fPIC", "-std=c++17", "-pthread",
               *_SRCS, "-o", tmp]
        try:
            subprocess.run(cmd, check=True, capture_output=True, timeout=120)
            os.replace(tmp, lib)
            return lib
        except (subprocess.SubprocessError, FileNotFoundError, OSError):
            continue
    return None


def load() -> Optional[ctypes.CDLL]:
    """Load (building if needed) the native runtime; None if unavailable."""
    global _lib, _tried
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        tmp_dir = _tmp_build_dir()
        name = _lib_name()
        candidates = [os.path.join(_BUILD_DIR, name)]
        if _dir_is_safe(tmp_dir):
            candidates.append(os.path.join(tmp_dir, name))
        path = next((p for p in candidates if os.path.exists(p)),
                    None) or _build(name)
        if path is None:
            return None
        try:
            lib = ctypes.CDLL(path)
        except OSError:
            return None
        lib.apex_tpu_fnv1a64.restype = ctypes.c_uint64
        _lib = lib
        return _lib


def available() -> bool:
    return load() is not None


_i64p = ctypes.POINTER(ctypes.c_int64)
_f32p = ctypes.POINTER(ctypes.c_float)


def _as_i64(arr) -> np.ndarray:
    return np.ascontiguousarray(np.asarray(arr, dtype=np.int64))


def pack_f32(arrays: Sequence[np.ndarray], offsets, padded_sizes,
             total: int, nthreads: int = 0) -> np.ndarray:
    """Pack per-parameter arrays into one zero-padded flat fp32 buffer
    (host-side twin of apex_tpu.ops.flat.flatten; native when possible)."""
    srcs = [np.ascontiguousarray(a, dtype=np.float32).ravel()
            for a in arrays]
    sizes = _as_i64([s.size for s in srcs])
    offs = _as_i64(offsets)
    pads = _as_i64(padded_sizes)
    dst = np.zeros((total,), np.float32)
    lib = load()
    if lib is None:  # numpy fallback
        for s, off in zip(srcs, offs):
            dst[off:off + s.size] = s
        return dst
    n = len(srcs)
    src_ptrs = (_f32p * n)(*[s.ctypes.data_as(_f32p) for s in srcs])
    lib.apex_tpu_pack_f32(src_ptrs, sizes.ctypes.data_as(_i64p),
                          offs.ctypes.data_as(_i64p),
                          pads.ctypes.data_as(_i64p),
                          ctypes.c_int(n), dst.ctypes.data_as(_f32p),
                          ctypes.c_int(nthreads))
    return dst


def unpack_f32(flat: np.ndarray, shapes, sizes, offsets,
               nthreads: int = 0) -> list[np.ndarray]:
    """Inverse of :func:`pack_f32`."""
    flat = np.ascontiguousarray(flat, dtype=np.float32)
    outs = [np.empty((int(sz),), np.float32) for sz in sizes]
    lib = load()
    if lib is None:
        for out, off in zip(outs, offsets):
            out[:] = flat[int(off):int(off) + out.size]
    else:
        n = len(outs)
        szs = _as_i64(sizes)
        offs = _as_i64(offsets)
        dst_ptrs = (_f32p * n)(*[o.ctypes.data_as(_f32p) for o in outs])
        lib.apex_tpu_unpack_f32(flat.ctypes.data_as(_f32p),
                                szs.ctypes.data_as(_i64p),
                                offs.ctypes.data_as(_i64p),
                                ctypes.c_int(n), dst_ptrs,
                                ctypes.c_int(nthreads))
    return [o.reshape(shape) for o, shape in zip(outs, shapes)]


def f32_to_bf16(src: np.ndarray, nthreads: int = 0) -> np.ndarray:
    """Bulk fp32 -> bf16 (RNE) returning uint16 bit patterns."""
    src = np.ascontiguousarray(src, dtype=np.float32).ravel()
    lib = load()
    if lib is None:
        bits = src.view(np.uint32)
        lsb = (bits >> 16) & 1
        return ((bits + 0x7FFF + lsb) >> 16).astype(np.uint16)
    dst = np.empty(src.shape, np.uint16)
    lib.apex_tpu_f32_to_bf16(
        src.ctypes.data_as(_f32p),
        dst.ctypes.data_as(ctypes.POINTER(ctypes.c_uint16)),
        ctypes.c_int64(src.size), ctypes.c_int(nthreads))
    return dst


def augment_u8(images: np.ndarray, indices, crop_offsets, flips,
               crop_hw: "tuple[int, int]", nthreads: int = 0) -> np.ndarray:
    """Gather + crop + horizontal-flip a uint8 NHWC batch in one threaded
    pass (the host data-loader hot loop; csrc/image_pipeline.cpp).

    images:       [n, h, w, c] uint8 pool
    indices:      [batch] int rows into the pool
    crop_offsets: [batch, 2] (top, left) ints
    flips:        [batch] bools
    Returns [batch, crop_h, crop_w, c] uint8. Numpy fallback is the
    definitional twin (and the parity oracle in tests)."""
    images = np.ascontiguousarray(images, dtype=np.uint8)
    if images.ndim != 4:
        raise ValueError(f"images must be [n, h, w, c], got {images.shape}")
    n, h, w, c = images.shape
    ch, cw = map(int, crop_hw)
    idx = np.ascontiguousarray(indices, np.int32).ravel()
    offs = np.ascontiguousarray(crop_offsets, np.int32).reshape(-1, 2)
    flp = np.ascontiguousarray(flips, np.uint8).ravel()
    batch = idx.size
    if offs.shape[0] != batch or flp.size != batch:
        raise ValueError("indices, crop_offsets, flips must agree in batch")
    if (idx < 0).any() or (idx >= n).any():
        raise ValueError("index out of range")
    if ((offs[:, 0] < 0).any() or (offs[:, 0] + ch > h).any()
            or (offs[:, 1] < 0).any() or (offs[:, 1] + cw > w).any()):
        raise ValueError(f"crop window exceeds image bounds ({h}x{w})")
    lib = load()
    if lib is None:  # numpy fallback (also the test oracle)
        out = np.empty((batch, ch, cw, c), np.uint8)
        for b in range(batch):
            t, l = int(offs[b, 0]), int(offs[b, 1])
            crop = images[idx[b], t:t + ch, l:l + cw, :]
            out[b] = crop[:, ::-1, :] if flp[b] else crop
        return out
    out = np.empty((batch, ch, cw, c), np.uint8)
    u8p = ctypes.POINTER(ctypes.c_uint8)
    i32p = ctypes.POINTER(ctypes.c_int32)
    lib.apex_tpu_augment_u8(
        images.ctypes.data_as(u8p), ctypes.c_int64(h), ctypes.c_int64(w),
        ctypes.c_int64(c), idx.ctypes.data_as(i32p),
        offs.ctypes.data_as(i32p), flp.ctypes.data_as(u8p),
        ctypes.c_int64(batch), ctypes.c_int64(ch), ctypes.c_int64(cw),
        out.ctypes.data_as(u8p), ctypes.c_int(nthreads))
    return out


def _parse_ppm_header(buf: bytes) -> "tuple[int, int, int]":
    """Pure-python twin of csrc parse_ppm_header: (h, w, payload_off)
    of a binary P6 blob, or ValueError. Grammar: ``P6`` ws width ws
    height ws 255 + ONE ws byte + payload; ``#`` comments between
    tokens."""
    if len(buf) < 2 or buf[:2] != b"P6":
        raise ValueError("not a P6 ppm")
    i, n = 2, len(buf)

    def skip_ws(i):
        while i < n:
            ch = buf[i:i + 1]
            if ch == b"#":
                while i < n and buf[i:i + 1] != b"\n":
                    i += 1
            elif ch in b" \t\r\n":
                i += 1
            else:
                break
        return i

    vals = []
    for _ in range(3):
        i = skip_ws(i)
        j = i
        while j < n and buf[j:j + 1].isdigit():
            j += 1
        if j == i:
            raise ValueError("malformed ppm header")
        vals.append(int(buf[i:j]))
        i = j
    w, h, maxval = vals
    if w <= 0 or h <= 0 or maxval != 255:
        raise ValueError(f"unsupported ppm (w={w}, h={h}, max={maxval})")
    if i >= n or buf[i:i + 1] not in b" \t\r\n":
        raise ValueError("malformed ppm header")
    i += 1
    if n - i < w * h * 3:
        raise ValueError("truncated ppm payload")
    return h, w, i


def ppm_dims(blob: bytes) -> "tuple[int, int]":
    """(h, w) of a binary P6 blob — the header probe the loader uses to
    draw crop offsets before the batched decode."""
    lib = load()
    if lib is None:
        h, w, _ = _parse_ppm_header(blob)
        return h, w
    h = ctypes.c_int64()
    w = ctypes.c_int64()
    rc = lib.apex_tpu_ppm_dims(
        ctypes.cast(ctypes.c_char_p(blob),
                    ctypes.POINTER(ctypes.c_uint8)),
        ctypes.c_int64(len(blob)), ctypes.byref(h), ctypes.byref(w))
    if rc != 0:
        raise ValueError(f"malformed ppm (native parse rc={rc})")
    return int(h.value), int(w.value)


def decode_ppm_augment_u8(blobs: "Sequence[bytes]", crop_offsets, flips,
                          crop_hw: "tuple[int, int]",
                          nthreads: int = 0) -> np.ndarray:
    """Decode + crop + horizontal-flip a batch of P6 blobs in one
    threaded native pass (csrc apex_tpu_decode_ppm_augment_u8) — the
    on-disk analog of :func:`augment_u8`. Offsets are validated against
    each image's OWN decoded dims. Returns [batch, ch, cw, 3] uint8.
    Pure-python fallback is the definitional twin (and test oracle)."""
    ch, cw = map(int, crop_hw)
    batch = len(blobs)
    offs = np.ascontiguousarray(crop_offsets, np.int32).reshape(-1, 2)
    flp = np.ascontiguousarray(flips, np.uint8).ravel()
    if offs.shape[0] != batch or flp.size != batch:
        raise ValueError("blobs, crop_offsets, flips must agree in batch")
    lib = load()
    if lib is None:  # fallback: per-image parse + numpy crop/flip
        out = np.empty((batch, ch, cw, 3), np.uint8)
        for b, blob in enumerate(blobs):
            h, w, off = _parse_ppm_header(blob)
            t, l = int(offs[b, 0]), int(offs[b, 1])
            if t < 0 or l < 0 or t + ch > h or l + cw > w:
                raise ValueError(
                    f"crop window exceeds image bounds at index {b} "
                    f"({h}x{w})")
            img = np.frombuffer(blob, np.uint8, count=h * w * 3,
                                offset=off).reshape(h, w, 3)
            crop = img[t:t + ch, l:l + cw]
            out[b] = crop[:, ::-1, :] if flp[b] else crop
        return out
    out = np.empty((batch, ch, cw, 3), np.uint8)
    u8p = ctypes.POINTER(ctypes.c_uint8)
    # keep the c_char_p buffers alive across the call
    bufs = [ctypes.c_char_p(bytes(blob)) for blob in blobs]
    ptrs = (u8p * batch)(*[ctypes.cast(bp, u8p) for bp in bufs])
    lens = _as_i64([len(b) for b in blobs])
    rc = lib.apex_tpu_decode_ppm_augment_u8(
        ptrs, lens.ctypes.data_as(_i64p), ctypes.c_int64(batch),
        offs.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        flp.ctypes.data_as(u8p), ctypes.c_int64(ch), ctypes.c_int64(cw),
        out.ctypes.data_as(u8p), ctypes.c_int(nthreads))
    if rc != 0:
        raise ValueError(
            f"ppm decode/crop failed at batch index {rc - 1} (malformed "
            f"blob or crop window exceeds image bounds)")
    return out


def fingerprint(data: np.ndarray) -> int:
    """FNV-1a 64 content hash (checkpoint integrity)."""
    buf = np.ascontiguousarray(data)
    view = buf.view(np.uint8).ravel()
    lib = load()
    if lib is None:
        h = np.uint64(1469598103934665603)
        p = np.uint64(1099511628211)
        with np.errstate(over="ignore"):
            for chunk in np.array_split(view, max(1, view.size // (1 << 20))):
                for b in chunk.tolist():
                    h = np.uint64((int(h) ^ b) * int(p) & 0xFFFFFFFFFFFFFFFF)
        return int(h)
    return int(lib.apex_tpu_fnv1a64(
        view.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        ctypes.c_int64(view.size)))
