"""ctypes bindings for the native outer-loop kernels (native/odtp_kernels.cpp).

Loads ``native/libodtp.so`` when present (``make -C native``), building it on
first use if a compiler is available; otherwise every entry point falls back
to numpy so the framework never hard-requires the native build.

The fused entry points matter most: ``f16_accumulate`` and
``dequant8_accumulate`` turn the butterfly collect step (decode + add over
multi-GB buffers) into a single parallel pass.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
from typing import Optional

import numpy as np

from opendiloco_tpu.utils.logger import get_text_logger

log = get_text_logger(__name__)

_NATIVE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    "native",
)
_LIB_PATH = os.path.join(_NATIVE_DIR, "libodtp.so")

_lib: Optional[ctypes.CDLL] = None
_tried = False


def _try_build() -> None:
    """Build the library where it is missing. A failed build is logged,
    not swallowed: the numpy fallback it leaves behind is correct but an
    order of magnitude slower, and nobody should run it unknowingly."""
    try:
        subprocess.run(
            ["make", "-C", _NATIVE_DIR, "-s"],
            check=True,
            capture_output=True,
            text=True,
            timeout=120,
        )
    except subprocess.CalledProcessError as e:
        log.warning(
            "native build failed (exit %d); using the numpy fallbacks:\n%s",
            e.returncode,
            (e.stderr or "").strip()[-2000:],
        )
    except (OSError, subprocess.TimeoutExpired) as e:
        log.warning(
            "native build did not run (%s: %s); using the numpy fallbacks",
            type(e).__name__,
            e,
        )


def get_lib() -> Optional[ctypes.CDLL]:
    """The loaded native library, or None (numpy fallback)."""
    global _lib, _tried
    if _lib is not None or _tried:
        return _lib
    _tried = True
    if not os.path.exists(_LIB_PATH) and os.environ.get(
        "OPENDILOCO_TPU_NO_NATIVE_BUILD"
    ) not in ("1", "true"):
        _try_build()
    if not os.path.exists(_LIB_PATH):
        return None
    try:
        lib = ctypes.CDLL(_LIB_PATH)
    except OSError as e:
        log.warning(
            "could not load %s (%s); using the numpy fallbacks", _LIB_PATH, e
        )
        return None
    u16p = ctypes.POINTER(ctypes.c_uint16)
    f32p = ctypes.POINTER(ctypes.c_float)
    i8p = ctypes.POINTER(ctypes.c_int8)
    st = ctypes.c_size_t
    lib.odtp_add_f32.argtypes = [f32p, f32p, st]
    lib.odtp_scale_f32.argtypes = [f32p, ctypes.c_float, st]
    lib.odtp_sub_f32.argtypes = [f32p, f32p, f32p, st]
    lib.odtp_f32_to_f16.argtypes = [f32p, u16p, st]
    lib.odtp_f16_to_f32.argtypes = [u16p, f32p, st]
    lib.odtp_f16_accumulate_f32.argtypes = [u16p, f32p, st]
    lib.odtp_quantize_blockwise_i8.argtypes = [f32p, i8p, f32p, st, st]
    lib.odtp_dequantize_blockwise_i8.argtypes = [i8p, f32p, f32p, st, st]
    lib.odtp_dequantize_blockwise_i8_accumulate.argtypes = [i8p, f32p, f32p, st, st]
    u8p = ctypes.POINTER(ctypes.c_uint8)
    lib.odtp_quantile_assign.argtypes = [f32p, f32p, u8p, st]
    lib.odtp_quantile_edges.argtypes = [f32p, st, f32p]
    lib.odtp_version.restype = ctypes.c_int
    try:  # version-2 kernels (a stale .so without them keeps the v1 surface)
        lib.odtp_quantize_uniform8.argtypes = [f32p, u8p, st, f32p, f32p]
        lib.odtp_dequantize_uniform8.argtypes = [
            u8p, ctypes.c_float, ctypes.c_float, f32p, st,
        ]
        lib.odtp_dequantize_uniform8_accumulate.argtypes = [
            u8p, ctypes.c_float, ctypes.c_float, f32p, st,
        ]
        lib.odtp_lut256_gather.argtypes = [u8p, f32p, f32p, st]
        lib.odtp_lut256_accumulate.argtypes = [u8p, f32p, f32p, st]
    except AttributeError:
        pass
    try:  # version-3 kernels (fused scaled-fp16 paths)
        lib.odtp_absmax_f32.argtypes = [f32p, st]
        lib.odtp_absmax_f32.restype = ctypes.c_float
        lib.odtp_f32_to_f16_scaled.argtypes = [f32p, ctypes.c_float, u16p, st]
        lib.odtp_f16_to_f32_scaled.argtypes = [u16p, ctypes.c_float, f32p, st]
        lib.odtp_f16_accumulate_scaled_f32.argtypes = [
            u16p, ctypes.c_float, f32p, st,
        ]
    except AttributeError:
        pass
    try:  # version-4 kernels (chunk-granular encode prescans)
        lib.odtp_minmax_f32.argtypes = [f32p, st, f32p, f32p]
        lib.odtp_quantize_uniform8_given.argtypes = [
            f32p, u8p, st, ctypes.c_float, ctypes.c_float,
        ]
    except AttributeError:
        pass
    try:  # version-5 kernels (fused outer SGD + sqnorm)
        lib.odtp_outer_sgd_f32.argtypes = [
            f32p, f32p, f32p, ctypes.c_float, ctypes.c_float, ctypes.c_int, st,
        ]
        lib.odtp_sqnorm_f32.argtypes = [f32p, st]
        lib.odtp_sqnorm_f32.restype = ctypes.c_double
    except AttributeError:
        pass
    try:  # version-6 kernels (4-bit blockwise codec)
        lib.odtp_quantize_blockwise4.argtypes = [f32p, u8p, u16p, st, st]
        lib.odtp_dequantize_blockwise4.argtypes = [u8p, u16p, f32p, st, st]
        lib.odtp_dequantize_blockwise4_accumulate.argtypes = [
            u8p, u16p, f32p, st, st,
        ]
    except AttributeError:
        pass
    for fn in (lib.odtp_sendall, lib.odtp_recvall):
        fn.argtypes = [ctypes.c_int, ctypes.c_void_p, st]
        fn.restype = ctypes.c_int
    _lib = lib
    return _lib


def available() -> bool:
    return get_lib() is not None


def _f32p(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_float))


def _u16p(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_uint16))


def _i8p(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_int8))


def _check_out(out: np.ndarray, n: int) -> None:
    """Decode destinations must be 1-D contiguous float32 of exactly n
    elements: the C kernels write n floats through a raw pointer (an
    undersized buffer would be heap corruption, not an exception), and the
    numpy fallbacks' reshape(-1) would silently copy (and discard the
    result) for non-contiguous ND views."""
    if out.dtype != np.float32 or out.ndim != 1 or not out.flags.c_contiguous:
        raise ValueError(
            "out must be a contiguous 1-D float32 array, got "
            f"dtype={out.dtype} ndim={out.ndim} contiguous={out.flags.c_contiguous}"
        )
    if out.size != n:
        raise ValueError(f"out holds {out.size} elements, need exactly {n}")


def _check_len(have: int, need: int, what: str) -> None:
    """The C kernels read exactly `need` elements; a short payload (peer
    bug, truncated transfer) must fail loudly, not read out of bounds."""
    if have < need:
        raise ValueError(f"{what}: payload holds {have} elements, need {need}")


# -- public ops (native with numpy fallback) --------------------------------


def add_inplace(dst: np.ndarray, src: np.ndarray) -> None:
    """dst += src over float32 buffers."""
    lib = get_lib()
    if lib is None or dst.dtype != np.float32 or not dst.flags.c_contiguous:
        np.add(dst, src, out=dst)
        return
    src = np.ascontiguousarray(src, np.float32)
    lib.odtp_add_f32(_f32p(dst), _f32p(src), dst.size)


def scale_inplace(dst: np.ndarray, s: float) -> None:
    lib = get_lib()
    if lib is None or dst.dtype != np.float32 or not dst.flags.c_contiguous:
        np.multiply(dst, s, out=dst)
        return
    lib.odtp_scale_f32(_f32p(dst), ctypes.c_float(s), dst.size)


def sub(
    a: np.ndarray, b: np.ndarray, out: Optional[np.ndarray] = None
) -> np.ndarray:
    """a - b -> float32 array (pseudo-gradient). ``out`` reuses a buffer:
    fresh multi-GB allocations every outer round hit kernel page-fault /
    compaction stalls (measured 0.1 GB/s worst case vs ~1 GB/s into an
    existing buffer), so the optimizer passes persistent buffers here."""
    lib = get_lib()
    a = np.ascontiguousarray(a, np.float32)
    b = np.ascontiguousarray(b, np.float32)
    if out is None or out.shape != a.shape or out.dtype != np.float32:
        out = np.empty_like(a)
    if lib is None:
        np.subtract(a, b, out=out)
        return out
    lib.odtp_sub_f32(_f32p(a), _f32p(b), _f32p(out), a.size)
    return out


def f32_to_f16_bytes(a: np.ndarray) -> bytes:
    lib = get_lib()
    a = np.ascontiguousarray(a, np.float32)
    if lib is None:
        return a.astype(np.float16).tobytes()
    out = np.empty(a.size, np.uint16)
    lib.odtp_f32_to_f16(_f32p(a.reshape(-1)), _u16p(out), a.size)
    return out.tobytes()


def f16_bytes_to_f32(
    payload: bytes, n: int, out: Optional[np.ndarray] = None
) -> np.ndarray:
    lib = get_lib()
    src = np.frombuffer(payload, np.uint16)
    _check_len(src.size, n, "f16_bytes_to_f32")
    if out is None:
        out = np.empty(n, np.float32)
    else:
        _check_out(out, n)
    if lib is None:
        out[:] = np.frombuffer(payload, np.float16)[:n]
        return out
    lib.odtp_f16_to_f32(_u16p(src), _f32p(out), n)
    return out


def f16_accumulate(payload: bytes, dst: np.ndarray) -> None:
    """dst += decode_f16(payload) in one fused pass."""
    lib = get_lib()
    _check_len(len(payload) // 2, dst.size, "f16_accumulate")
    if lib is None or dst.dtype != np.float32 or not dst.flags.c_contiguous:
        dst += np.frombuffer(payload, np.float16).astype(np.float32).reshape(dst.shape)
        return
    src = np.frombuffer(payload, np.uint16)
    lib.odtp_f16_accumulate_f32(_u16p(src), _f32p(dst), dst.size)


def absmax(a: np.ndarray) -> float:
    """max(|a|) in one pass with no temporary abs array (NaNs skipped)."""
    lib = get_lib()
    a = np.ascontiguousarray(a, np.float32).reshape(-1)
    if not _has(lib, "odtp_absmax_f32"):
        return float(np.max(np.abs(a))) if a.size else 0.0
    return float(lib.odtp_absmax_f32(_f32p(a), a.size))


def f32_to_f16_scaled_bytes(a: np.ndarray, scale: float) -> bytes:
    """f16(a / scale) fused into one pass (scaled-fp16 encode); bit-equal
    to the fallback's explicit division."""
    lib = get_lib()
    a = np.ascontiguousarray(a, np.float32).reshape(-1)
    if not _has(lib, "odtp_f32_to_f16_scaled"):
        return (a / np.float32(scale)).astype(np.float16).tobytes()
    out = np.empty(a.size, np.uint16)
    lib.odtp_f32_to_f16_scaled(
        _f32p(a), ctypes.c_float(scale), _u16p(out), a.size
    )
    return out.tobytes()


def f16_bytes_to_f32_scaled(
    payload: bytes, scale: float, n: int, out: Optional[np.ndarray] = None
) -> np.ndarray:
    """decode_f16(payload) * scale in one fused pass."""
    lib = get_lib()
    src = np.frombuffer(payload, np.uint16)
    _check_len(src.size, n, "f16_bytes_to_f32_scaled")
    if out is None:
        out = np.empty(n, np.float32)
    else:
        _check_out(out, n)
    if not _has(lib, "odtp_f16_to_f32_scaled"):
        np.multiply(
            np.frombuffer(payload, np.float16)[:n].astype(np.float32),
            np.float32(scale),
            out=out,
        )
        return out
    lib.odtp_f16_to_f32_scaled(_u16p(src), ctypes.c_float(scale), _f32p(out), n)
    return out


def f16_accumulate_scaled(payload: bytes, scale: float, dst: np.ndarray) -> None:
    """dst += decode_f16(payload) * scale in one fused pass."""
    lib = get_lib()
    _check_len(len(payload) // 2, dst.size, "f16_accumulate_scaled")
    if (
        not _has(lib, "odtp_f16_accumulate_scaled_f32")
        or dst.dtype != np.float32
        or not dst.flags.c_contiguous
    ):
        dst += (
            np.frombuffer(payload, np.float16)[: dst.size]
            .astype(np.float32)
            .reshape(dst.shape)
            * np.float32(scale)
        )
        return
    src = np.frombuffer(payload, np.uint16)
    lib.odtp_f16_accumulate_scaled_f32(
        _u16p(src), ctypes.c_float(scale), _f32p(dst), dst.size
    )


def quantize_blockwise(a: np.ndarray, block: int) -> tuple[bytes, bytes]:
    """-> (int8 payload, float32 scales payload)."""
    lib = get_lib()
    a = np.ascontiguousarray(a, np.float32).reshape(-1)
    nblocks = (a.size + block - 1) // block
    if lib is None:
        pad = (-a.size) % block
        padded = np.pad(a, (0, pad)).reshape(-1, block)
        scales = np.max(np.abs(padded), axis=1)
        scales[scales == 0] = 1.0
        q = np.clip(
            np.round(padded / scales[:, None] * 127.0), -127, 127
        ).astype(np.int8)
        return q.reshape(-1)[: a.size].tobytes(), scales.astype(np.float32).tobytes()
    q = np.empty(a.size, np.int8)
    scales = np.empty(nblocks, np.float32)
    lib.odtp_quantize_blockwise_i8(_f32p(a), _i8p(q), _f32p(scales), a.size, block)
    return q.tobytes(), scales.tobytes()


def dequantize_blockwise(
    payload: bytes, scales_payload: bytes, n: int, block: int,
    out: Optional[np.ndarray] = None,
) -> np.ndarray:
    lib = get_lib()
    q = np.frombuffer(payload, np.int8)
    scales = np.frombuffer(scales_payload, np.float32)
    _check_len(q.size, n, "dequantize_blockwise")
    _check_len(scales.size, (n + block - 1) // block, "dequantize_blockwise scales")
    if out is None:
        out = np.empty(n, np.float32)
    else:
        _check_out(out, n)
    if lib is None:
        pad = (-n) % block
        qp = np.pad(q[:n].astype(np.float32), (0, pad)).reshape(-1, block)
        dec = qp * (scales[: qp.shape[0], None] / 127.0)
        out[:] = dec.reshape(-1)[:n]
        return out
    lib.odtp_dequantize_blockwise_i8(_i8p(q), _f32p(scales), _f32p(out), n, block)
    return out


def dequant8_accumulate(payload: bytes, scales_payload: bytes, dst: np.ndarray, block: int) -> None:
    """dst += dequantize_blockwise(payload) in one fused pass."""
    lib = get_lib()
    _check_len(len(payload), dst.size, "dequant8_accumulate")
    _check_len(
        len(scales_payload) // 4,
        (dst.size + block - 1) // block,
        "dequant8_accumulate scales",
    )
    if lib is None or dst.dtype != np.float32 or not dst.flags.c_contiguous:
        dst += dequantize_blockwise(payload, scales_payload, dst.size, block).reshape(
            dst.shape
        )
        return
    q = np.frombuffer(payload, np.int8)
    scales = np.frombuffer(scales_payload, np.float32)
    lib.odtp_dequantize_blockwise_i8_accumulate(
        _i8p(q), _f32p(scales), _f32p(dst), dst.size, block
    )


def quantize_blockwise4(a: np.ndarray, block: int) -> tuple[bytes, bytes]:
    """4-bit blockwise quantize -> (packed nibble payload, fp16 scales
    payload). Element 2i is the low nibble of byte i, element 2i+1 the high
    nibble; an odd tail leaves the final high nibble 0 (NOT quantized zero,
    which would be 8). Quantization runs against the fp16-ROUNDED scale so
    encode and decode use the same value. ``block`` must be even so block
    boundaries land on byte boundaries."""
    if block % 2:
        raise ValueError(f"block must be even for nibble packing, got {block}")
    lib = get_lib()
    a = np.ascontiguousarray(a, np.float32).reshape(-1)
    nblocks = (a.size + block - 1) // block
    if not _has(lib, "odtp_quantize_blockwise4"):
        pad = (-a.size) % block
        padded = np.pad(a, (0, pad)).reshape(-1, block)
        amax = np.max(np.abs(padded), axis=1) if nblocks else np.zeros(0, np.float32)
        s = np.where(amax > 0, amax, np.float32(1.0)).astype(np.float32)
        # clamp into the fp16 normal range, same as the C kernel: an amax
        # above 65504 would round to f16 inf (NaN payload on decode), one
        # below the min normal would flush the whole block
        np.clip(s, np.float32(6.1035156e-05), np.float32(65504.0), out=s)
        s16 = s.astype(np.float16)
        inv = np.float32(7.0) / s16.astype(np.float32)
        q = np.clip(np.round(padded * inv[:, None]), -7, 7)
        nib = (q.reshape(-1)[: a.size] + 8).astype(np.uint8)
        if a.size % 2:
            nib = np.append(nib, np.uint8(0))
        packed = nib[0::2] | (nib[1::2] << 4)
        return packed.tobytes(), s16.view(np.uint16).tobytes()
    packed = np.empty((a.size + 1) // 2, np.uint8)
    scales = np.empty(nblocks, np.uint16)
    lib.odtp_quantize_blockwise4(
        _f32p(a), _u8p(packed), _u16p(scales), a.size, block
    )
    return packed.tobytes(), scales.tobytes()


def _dequant4_numpy(
    packed: np.ndarray, scales: np.ndarray, n: int, block: int
) -> np.ndarray:
    nib = np.empty(2 * packed.size, np.uint8)
    nib[0::2] = packed & 0x0F
    nib[1::2] = packed >> 4
    q = nib[:n].astype(np.float32) - np.float32(8.0)
    s = scales[: (n + block - 1) // block].view(np.float16).astype(
        np.float32
    ) / np.float32(7.0)
    qp = np.pad(q, (0, (-n) % block)).reshape(-1, block)
    return (qp * s[:, None]).reshape(-1)[:n]


def dequantize_blockwise4(
    payload: bytes, scales_payload: bytes, n: int, block: int,
    out: Optional[np.ndarray] = None,
) -> np.ndarray:
    if block % 2:
        raise ValueError(f"block must be even for nibble packing, got {block}")
    lib = get_lib()
    packed = np.frombuffer(payload, np.uint8)
    scales = np.frombuffer(scales_payload, np.uint16)
    _check_len(packed.size, (n + 1) // 2, "dequantize_blockwise4")
    _check_len(scales.size, (n + block - 1) // block, "dequantize_blockwise4 scales")
    if out is None:
        out = np.empty(n, np.float32)
    else:
        _check_out(out, n)
    if not _has(lib, "odtp_dequantize_blockwise4"):
        out[:] = _dequant4_numpy(packed, scales, n, block)
        return out
    lib.odtp_dequantize_blockwise4(_u8p(packed), _u16p(scales), _f32p(out), n, block)
    return out


def dequant4_accumulate(
    payload: bytes, scales_payload: bytes, dst: np.ndarray, block: int
) -> None:
    """dst += dequantize_blockwise4(payload) in one fused pass."""
    lib = get_lib()
    packed = np.frombuffer(payload, np.uint8)
    scales = np.frombuffer(scales_payload, np.uint16)
    _check_len(packed.size, (dst.size + 1) // 2, "dequant4_accumulate")
    _check_len(
        scales.size,
        (dst.size + block - 1) // block,
        "dequant4_accumulate scales",
    )
    if (
        not _has(lib, "odtp_dequantize_blockwise4_accumulate")
        or dst.dtype != np.float32
        or not dst.flags.c_contiguous
    ):
        dst += _dequant4_numpy(packed, scales, dst.size, block).reshape(dst.shape)
        return
    lib.odtp_dequantize_blockwise4_accumulate(
        _u8p(packed), _u16p(scales), _f32p(dst), dst.size, block
    )


def _u8p(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))


def _has(lib, name: str) -> bool:
    try:
        return lib is not None and getattr(lib, name) is not None
    except AttributeError:  # stale .so predating the symbol
        return False


def quantize_uniform8(a: np.ndarray) -> tuple[bytes, float, float]:
    """Linear lo/span uint8 quantization -> (payload, lo, span); min/max
    reduction and quantize both native single passes when built.

    NaN caveat (mirrors ``absmax``): the C kernel's min/max reduction skips
    NaNs (finite lo/span, NaN elements clamp arbitrarily), while the numpy
    fallback's ``a.min()/a.max()`` propagate NaN into lo/span and hence the
    whole payload. NaN gradients are already a broken upstream state (the
    fp16 scaler skips the step), so the two paths are only bit-identical on
    finite inputs -- which is what the parity tests assert."""
    a = np.ascontiguousarray(a, np.float32).reshape(-1)
    lib = get_lib()
    if not _has(lib, "odtp_quantize_uniform8"):
        lo = float(a.min()) if a.size else 0.0
        hi = float(a.max()) if a.size else 0.0
        span = (hi - lo) or 1.0
        # same expression ORDER as the C kernel ((x-lo) * (255/span), f32):
        # a different order can differ by 1 ulp at .5 rounding boundaries
        # and flip a bucket, breaking native-vs-fallback bit-equality
        inv = np.float32(255.0) / np.float32(span)
        q = np.clip(
            np.round((a - np.float32(lo)) * inv), 0, 255
        ).astype(np.uint8)
        return q.tobytes(), lo, span
    q = np.empty(a.size, np.uint8)
    lo_out = np.empty(1, np.float32)
    span_out = np.empty(1, np.float32)
    lib.odtp_quantize_uniform8(
        _f32p(a), _u8p(q), a.size, _f32p(lo_out), _f32p(span_out)
    )
    return q.tobytes(), float(lo_out[0]), float(span_out[0])


def minmax_span(a: np.ndarray) -> tuple[float, float]:
    """(lo, span) of ``a`` with the same reduction, arithmetic precision,
    and zero-span fix-up as ``quantize_uniform8``, so a chunked encode fed
    by this prescan is bit-identical to the fused whole-tensor kernel on
    the matching build (native-vs-native, fallback-vs-fallback)."""
    a = np.ascontiguousarray(a, np.float32).reshape(-1)
    lib = get_lib()
    if not _has(lib, "odtp_minmax_f32"):
        lo = float(a.min()) if a.size else 0.0
        hi = float(a.max()) if a.size else 0.0
        span = (hi - lo) or 1.0
        return lo, span
    lo_out = np.empty(1, np.float32)
    hi_out = np.empty(1, np.float32)
    lib.odtp_minmax_f32(_f32p(a), a.size, _f32p(lo_out), _f32p(hi_out))
    # f32 subtraction, exactly as the C kernel computes span
    span = np.float32(hi_out[0]) - np.float32(lo_out[0])
    if not (span > 0):
        span = np.float32(1.0)
    return float(lo_out[0]), float(span)


def quantize_uniform8_given(a: np.ndarray, lo: float, span: float) -> bytes:
    """Quantize ``a`` with a precomputed (lo, span) — the per-chunk half of
    the prescan/quantize split. Expression order matches the fused kernel
    (and the ``quantize_uniform8`` fallback) for bit-parity."""
    a = np.ascontiguousarray(a, np.float32).reshape(-1)
    lib = get_lib()
    if not _has(lib, "odtp_quantize_uniform8_given"):
        inv = np.float32(255.0) / np.float32(span)
        q = np.clip(
            np.round((a - np.float32(lo)) * inv), 0, 255
        ).astype(np.uint8)
        return q.tobytes()
    q = np.empty(a.size, np.uint8)
    lib.odtp_quantize_uniform8_given(
        _f32p(a), _u8p(q), a.size, ctypes.c_float(lo), ctypes.c_float(span)
    )
    return q.tobytes()


def dequantize_uniform8(
    payload: bytes, lo: float, span: float, n: int,
    out: Optional[np.ndarray] = None,
) -> np.ndarray:
    """Single-pass uniform8 decode, optionally straight into ``out``."""
    q = np.frombuffer(payload, np.uint8)
    _check_len(q.size, n, "dequantize_uniform8")
    lib = get_lib()
    if out is None:
        out = np.empty(n, np.float32)
    else:
        _check_out(out, n)
    if not _has(lib, "odtp_dequantize_uniform8"):
        np.multiply(q[:n].astype(np.float32), span / 255.0, out=out)
        out += lo
        return out
    lib.odtp_dequantize_uniform8(
        _u8p(q), ctypes.c_float(lo), ctypes.c_float(span), _f32p(out), n
    )
    return out


def dequant_uniform8_accumulate(
    payload: bytes, lo: float, span: float, dst: np.ndarray
) -> None:
    """dst += uniform8_decode(payload) in one fused pass."""
    lib = get_lib()
    _check_len(len(payload), dst.size, "dequant_uniform8_accumulate")
    if (
        not _has(lib, "odtp_dequantize_uniform8_accumulate")
        or dst.dtype != np.float32
        or not dst.flags.c_contiguous
    ):
        q = np.frombuffer(payload, np.uint8)
        dst += (q.astype(np.float32) * (span / 255.0) + lo).reshape(dst.shape)
        return
    lib.odtp_dequantize_uniform8_accumulate(
        _u8p(np.frombuffer(payload, np.uint8)),
        ctypes.c_float(lo),
        ctypes.c_float(span),
        _f32p(dst),
        dst.size,
    )


def lut256_gather(
    idx_payload: bytes, lut: np.ndarray, n: int,
    out: Optional[np.ndarray] = None,
) -> np.ndarray:
    """out = lut[idx] (quantile codebook decode), optionally into ``out``."""
    idx = np.frombuffer(idx_payload, np.uint8)
    _check_len(idx.size, n, "lut256_gather")
    lut = np.ascontiguousarray(lut, np.float32)
    _check_len(lut.size, 256, "lut256_gather codebook")
    lib = get_lib()
    if out is None:
        out = np.empty(n, np.float32)
    else:
        _check_out(out, n)
    if not _has(lib, "odtp_lut256_gather"):
        np.take(lut, idx[:n], out=out)
        return out
    lib.odtp_lut256_gather(_u8p(idx), _f32p(lut), _f32p(out), n)
    return out


def lut256_accumulate(
    idx_payload: bytes, lut: np.ndarray, dst: np.ndarray
) -> None:
    """dst += lut[idx] in one fused pass."""
    idx = np.frombuffer(idx_payload, np.uint8)
    _check_len(idx.size, dst.size, "lut256_accumulate")
    lut = np.ascontiguousarray(lut, np.float32)
    _check_len(lut.size, 256, "lut256_accumulate codebook")
    lib = get_lib()
    if (
        not _has(lib, "odtp_lut256_accumulate")
        or dst.dtype != np.float32
        or not dst.flags.c_contiguous
    ):
        dst += lut[idx].reshape(dst.shape)
        return
    lib.odtp_lut256_accumulate(_u8p(idx), _f32p(lut), _f32p(dst), dst.size)


def quantile_assign(flat: np.ndarray, inner_edges: np.ndarray) -> np.ndarray:
    """Assign each value to one of 256 buckets split by 255 sorted inner
    edges (searchsorted side='right' semantics)."""
    lib = get_lib()
    flat = np.ascontiguousarray(flat, np.float32)
    inner_edges = np.ascontiguousarray(inner_edges, np.float32)
    if lib is None:
        return np.clip(
            np.searchsorted(inner_edges, flat, side="right"), 0, 255
        ).astype(np.uint8)
    out = np.empty(flat.size, np.uint8)
    lib.odtp_quantile_assign(
        _f32p(flat),
        _f32p(inner_edges),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        flat.size,
    )
    return out


def sock_sendall(sock, buf) -> None:
    """Send an entire contiguous buffer on a connected socket. Native path
    pumps bytes in C with the GIL released; fallback is socket.sendall
    (also zero-copy for memoryview/ndarray)."""
    lib = get_lib()
    if lib is None:
        sock.sendall(buf if isinstance(buf, (bytes, memoryview)) else memoryview(buf))
        return
    a = np.frombuffer(buf, np.uint8)  # zero-copy view, works read-only
    rc = lib.odtp_sendall(sock.fileno(), ctypes.c_void_p(a.ctypes.data), a.size)
    if rc != 0:
        raise OSError(-rc, f"odtp_sendall failed (rc={rc})")


def sock_recvall(sock, buf: np.ndarray) -> None:
    """Receive exactly len(buf) bytes into a writable contiguous buffer."""
    lib = get_lib()
    if lib is None:
        view = memoryview(buf).cast("B")
        got = 0
        while got < len(view):
            r = sock.recv_into(view[got:])
            if r == 0:
                raise ConnectionResetError("peer closed mid-transfer")
            got += r
        return
    a = np.frombuffer(buf, np.uint8)
    rc = lib.odtp_recvall(sock.fileno(), ctypes.c_void_p(a.ctypes.data), a.size)
    if rc == -1:
        raise ConnectionResetError("peer closed mid-transfer")
    if rc != 0:
        raise OSError(-rc, f"odtp_recvall failed (rc={rc})")


def quantile_edges(flat: np.ndarray) -> np.ndarray:
    """257 quantile edges of a strided <=100k sample of ``flat`` (the
    codebook build of the quantile8bit codec), float32."""
    lib = get_lib()
    flat = np.ascontiguousarray(flat, np.float32).reshape(-1)
    if lib is None:
        cap = 100_000
        if flat.size <= cap:
            sample = flat
        else:
            stride = flat.size / cap
            sample = flat[(np.arange(cap) * stride).astype(np.int64)]
        return np.quantile(sample, np.linspace(0, 1, 257)).astype(np.float32)
    out = np.empty(257, np.float32)
    lib.odtp_quantile_edges(_f32p(flat), flat.size, _f32p(out))
    return out


def outer_sgd_step(
    p: np.ndarray,
    g: np.ndarray,
    buf: np.ndarray,
    lr: float,
    momentum: float,
    nesterov: bool,
) -> bool:
    """Fused momentum outer-SGD update of one leaf, all in place:
    ``buf = momentum*buf + g; p -= lr*(g + momentum*buf | buf)``.
    Returns False when the native path can't run (no lib, stale .so, or a
    non-contiguous/non-f32 in-place target) — caller keeps the numpy body.
    ``p`` and ``buf`` must be written through, so unlike the codec wrappers
    there is no ascontiguousarray coercion on them (a coerced copy would
    discard the update)."""
    lib = get_lib()
    if (
        not _has(lib, "odtp_outer_sgd_f32")
        or p.dtype != np.float32
        or buf.dtype != np.float32
        or not p.flags.c_contiguous
        or not buf.flags.c_contiguous
        or g.shape != p.shape
        or buf.shape != p.shape
    ):
        return False
    g = np.ascontiguousarray(g, np.float32)
    lib.odtp_outer_sgd_f32(
        _f32p(p),
        _f32p(g),
        _f32p(buf),
        ctypes.c_float(lr),
        ctypes.c_float(momentum),
        ctypes.c_int(1 if nesterov else 0),
        p.size,
    )
    return True


def sqnorm(a: np.ndarray) -> float:
    """sum(a*a) with a double accumulator (one OMP reduction pass); the
    pseudo_grad_norm gauge's per-leaf term."""
    lib = get_lib()
    a = np.ascontiguousarray(a, np.float32).reshape(-1)
    if not _has(lib, "odtp_sqnorm_f32"):
        v = a.astype(np.float64, copy=False)
        return float(np.dot(v, v))
    return float(lib.odtp_sqnorm_f32(_f32p(a), a.size))
