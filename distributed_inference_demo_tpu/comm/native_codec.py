"""ctypes bindings for the native wire codec (native/codec.cc).

Byte-compatible with the pure-Python codec in ``wire.py``; `available()`
is False only on a machine without a C++ compiler, where callers use
the Python codec.  The
reference's equivalent layer is the JNI bridge over ``utils.cpp``
(``native-lib.cpp:662-694``); here the binding is ctypes because pybind11
isn't in the image.
"""

from __future__ import annotations

import ctypes
from typing import List, Optional, Sequence

import numpy as np

from .wire import (_HEADER, _TO_NP, DType, TensorMessage, WireError,
                   _np_dtype_to_wire, payload_checksum, verify_checksum)

_lib: Optional[ctypes.CDLL] = None
_load_failed = False


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _load_failed
    if _lib is not None or _load_failed:
        return _lib
    from .native.build import NativeUnavailable, build
    try:
        path = build()
    except NativeUnavailable:
        # no compiler on this machine: the pure-Python codec serves.  A
        # build that FAILS, or a library that does not load, raises —
        # neither is a reason to run the other codec unseen
        _load_failed = True
        return None
    lib = ctypes.CDLL(str(path))
    u8p = ctypes.POINTER(ctypes.c_uint8)
    u64p = ctypes.POINTER(ctypes.c_uint64)
    lib.dwt_serialized_size.restype = ctypes.c_uint64
    lib.dwt_serialized_size.argtypes = [
        ctypes.c_uint32, u8p, u8p, ctypes.POINTER(u64p)]
    lib.dwt_serialize.restype = ctypes.c_uint64
    lib.dwt_serialize.argtypes = [
        ctypes.c_uint32, u8p, u8p, ctypes.POINTER(u64p),
        ctypes.POINTER(u8p), ctypes.c_uint8, u8p, ctypes.c_uint64]
    lib.dwt_open.restype = ctypes.c_void_p
    lib.dwt_open.argtypes = [u8p, ctypes.c_uint64]
    lib.dwt_ntensors.restype = ctypes.c_uint32
    lib.dwt_ntensors.argtypes = [ctypes.c_void_p]
    lib.dwt_flags.restype = ctypes.c_uint8
    lib.dwt_flags.argtypes = [ctypes.c_void_p]
    lib.dwt_tensor_info.restype = ctypes.c_int
    lib.dwt_tensor_info.argtypes = [
        ctypes.c_void_p, ctypes.c_uint32, u8p, u8p, u64p, u64p,
        ctypes.c_uint8]
    lib.dwt_tensor_data.restype = u8p
    lib.dwt_tensor_data.argtypes = [ctypes.c_void_p, ctypes.c_uint32]
    lib.dwt_close.restype = None
    lib.dwt_close.argtypes = [ctypes.c_void_p]
    _lib = lib
    return _lib


def available() -> bool:
    return _load() is not None


def serialize_tensors(arrays: Sequence[np.ndarray], flags: int = 0,
                      checksum: bool = True) -> bytes:
    lib = _load()
    if lib is None:
        raise WireError("native codec not available")
    def _contig(x):
        x = np.asarray(x)
        # Wire format is little-endian (wire.py does the same normalization);
        # byteswap any big-endian input before handing raw bytes to C++.
        if x.dtype.byteorder == ">":
            x = x.astype(x.dtype.newbyteorder("<"))
        # ascontiguousarray would promote 0-d to 1-d; 0-d is always contiguous
        return x if x.flags["C_CONTIGUOUS"] else np.ascontiguousarray(x)

    arrays = [_contig(a) for a in arrays]
    n = len(arrays)
    dtypes = (ctypes.c_uint8 * n)(*[int(_np_dtype_to_wire(a.dtype))
                                    for a in arrays])
    ndims = (ctypes.c_uint8 * n)(*[a.ndim for a in arrays])
    dim_arrays = [(ctypes.c_uint64 * a.ndim)(*a.shape) for a in arrays]
    dims = (ctypes.POINTER(ctypes.c_uint64) * n)(
        *[ctypes.cast(d, ctypes.POINTER(ctypes.c_uint64))
          for d in dim_arrays])
    datas = (ctypes.POINTER(ctypes.c_uint8) * n)(
        *[ctypes.cast(a.ctypes.data, ctypes.POINTER(ctypes.c_uint8))
          for a in arrays])
    size = lib.dwt_serialized_size(n, dtypes, ndims, dims)
    if size == 0 and n > 0:
        raise WireError("native serializer rejected input")
    out = ctypes.create_string_buffer(size)
    written = lib.dwt_serialize(
        n, dtypes, ndims, dims, datas, flags & 0xFF,
        ctypes.cast(out, ctypes.POINTER(ctypes.c_uint8)), size)
    if written != size:
        raise WireError(f"native serializer wrote {written}, expected {size}")
    if not checksum:
        return out.raw
    # The C codec writes 0 into the header's 16-bit field; the binding
    # stamps the payload checksum (wire.payload_checksum — the ONE owner
    # of the math) so native and Python frames stay byte-identical.
    # zlib.crc32 runs at C speed, so there is no native-side win to chase.
    import struct as _struct
    buf = bytearray(out.raw)
    _struct.pack_into("<H", buf, 6,
                      payload_checksum(memoryview(buf)[_HEADER.size:]))
    return bytes(buf)


def deserialize_tensors(data: bytes) -> TensorMessage:
    lib = _load()
    if lib is None:
        raise WireError("native codec not available")
    # Same integrity contract as wire.deserialize_tensors: a nonzero
    # header checksum is verified BEFORE the C decoder touches any tensor
    # (WireIntegrityError, never garbage); zero = pre-checksum peer.
    verify_checksum(data)
    # Zero-copy handoff: c_char_p keeps a reference to `data`; dwt_open makes
    # its own owned copy, so no Python-side staging copy is needed.
    buf = ctypes.cast(ctypes.c_char_p(data),
                      ctypes.POINTER(ctypes.c_uint8))
    h = lib.dwt_open(buf, len(data))
    if not h:
        raise WireError("native codec rejected message")
    try:
        n = lib.dwt_ntensors(h)
        flags = lib.dwt_flags(h)
        out: List[np.ndarray] = []
        for i in range(n):
            dt = ctypes.c_uint8()
            nd = ctypes.c_uint8()
            nbytes = ctypes.c_uint64()
            dims = (ctypes.c_uint64 * 16)()
            ok = lib.dwt_tensor_info(
                h, i, ctypes.byref(dt), ctypes.byref(nd),
                ctypes.byref(nbytes), dims, 16)
            if not ok or nd.value > 16:
                raise WireError("native codec: bad tensor info")
            np_dt = _TO_NP[DType(dt.value)]
            ptr = lib.dwt_tensor_data(h, i)
            shape = tuple(dims[d] for d in range(nd.value))
            # Single copy, straight from the C++ buffer into the final
            # writable array (no string_at staging + trailing .copy()).
            arr = np.empty(shape, np_dt)
            if nbytes.value:
                ctypes.memmove(arr.ctypes.data, ptr, nbytes.value)
            out.append(arr)
        return TensorMessage(tensors=out, flags=flags)
    finally:
        lib.dwt_close(h)
