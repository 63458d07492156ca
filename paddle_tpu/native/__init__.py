"""Native (C++) runtime components, loaded via ctypes.

Reference native parts this covers: paddle/fluid/recordio/ (chunked CRC'd
record files) and the MultiSlot parsing hot path of
paddle/fluid/framework/data_feed.cc.  The library builds on first use
with g++, from the committed source, into ``<checkout>/.native_build``
(gitignored) under a name that carries the source's hash — a stale or
foreign ``.so`` can never be picked up.  When no toolchain is available
a pure-Python fallback keeps the API working; ``native_available()``
says which one a run got.
"""
from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import tempfile
from typing import Iterator, List, Optional, Tuple

import numpy as np

__all__ = ["RecordIOWriter", "RecordIOScanner", "parse_multislot", "native_available"]

_lib = None
_tried = False


_BUILD_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".native_build")


def _build_and_open(src_name: str, lib_name: str,
                    extra_flags=()) -> Optional[ctypes.CDLL]:
    """Compile ``native/<src_name>`` into ``_BUILD_DIR`` and open it;
    None when there is no working toolchain.  The file name carries the
    source's sha1, so an existing file IS this source's build; the
    compile goes to a temp name and is renamed into place, so concurrent
    first uses never load a half-written library."""
    src = os.path.join(os.path.dirname(__file__), src_name)
    with open(src, "rb") as f:
        digest = hashlib.sha1(f.read()).hexdigest()[:12]
    so_path = os.path.join(_BUILD_DIR, "%s-%s.so" % (lib_name, digest))
    if not os.path.exists(so_path):
        os.makedirs(_BUILD_DIR, exist_ok=True)
        fd, tmp = tempfile.mkstemp(suffix=".so", dir=_BUILD_DIR)
        os.close(fd)
        try:
            subprocess.run(
                ["g++", "-O2", "-shared", "-fPIC", "-std=c++17", src,
                 "-o", tmp, *extra_flags],
                check=True, capture_output=True)
            os.replace(tmp, so_path)
        except (OSError, subprocess.CalledProcessError):
            return None
        finally:
            if os.path.exists(tmp):
                os.remove(tmp)
    try:
        return ctypes.CDLL(so_path)
    except OSError:
        return None


def _build_and_load() -> Optional[ctypes.CDLL]:
    global _lib, _tried
    if _tried:
        return _lib
    _tried = True
    lib = _build_and_open("recordio.cc", "libpaddle_tpu_native", ("-lz",))
    if lib is None:
        return None
    lib.recordio_writer_create.restype = ctypes.c_void_p
    lib.recordio_writer_create.argtypes = [ctypes.c_char_p, ctypes.c_int, ctypes.c_int]
    lib.recordio_writer_write.restype = ctypes.c_int
    lib.recordio_writer_write.argtypes = [ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int]
    lib.recordio_writer_close.restype = ctypes.c_int
    lib.recordio_writer_close.argtypes = [ctypes.c_void_p]
    lib.recordio_scanner_create.restype = ctypes.c_void_p
    lib.recordio_scanner_create.argtypes = [ctypes.c_char_p]
    lib.recordio_scanner_next.restype = ctypes.POINTER(ctypes.c_char)
    lib.recordio_scanner_next.argtypes = [ctypes.c_void_p, ctypes.POINTER(ctypes.c_int)]
    lib.recordio_scanner_close.argtypes = [ctypes.c_void_p]
    lib.multislot_parse.restype = ctypes.c_void_p
    lib.multislot_parse.argtypes = [
        ctypes.c_char_p, ctypes.c_long, ctypes.c_int, ctypes.POINTER(ctypes.c_int),
    ]
    lib.multislot_slot_size.restype = ctypes.c_long
    lib.multislot_slot_size.argtypes = [ctypes.c_void_p, ctypes.c_int]
    lib.multislot_copy_slot.argtypes = [
        ctypes.c_void_p, ctypes.c_int,
        ctypes.POINTER(ctypes.c_float), ctypes.POINTER(ctypes.c_int32),
    ]
    lib.multislot_free.argtypes = [ctypes.c_void_p]
    _lib = lib
    return lib


def native_available() -> bool:
    return _build_and_load() is not None


class RecordIOWriter:
    """reference: recordio/writer.cc."""

    def __init__(self, path: str, compress: bool = True, max_chunk_bytes: int = 1 << 20):
        self._lib = _build_and_load()
        self._path = path
        if self._lib is not None:
            self._h = self._lib.recordio_writer_create(
                path.encode(), int(compress), max_chunk_bytes
            )
            if not self._h:
                raise IOError("cannot open %s" % path)
        else:  # python fallback: naive framed file
            self._f = open(path, "wb")
            self._f.write(b"PYRIO\x00")

    def write(self, record: bytes) -> None:
        if self._lib is not None:
            rc = self._lib.recordio_writer_write(self._h, record, len(record))
            if rc != 0:
                raise IOError("recordio write failed")
        else:
            self._f.write(len(record).to_bytes(4, "little") + record)

    def close(self) -> None:
        if self._lib is not None:
            if self._lib.recordio_writer_close(self._h) != 0:
                raise IOError("recordio flush failed")
        else:
            self._f.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False


class RecordIOScanner:
    """reference: recordio/scanner.cc."""

    def __init__(self, path: str):
        self._lib = _build_and_load()
        self._path = path
        if self._lib is not None:
            self._h = self._lib.recordio_scanner_create(path.encode())
            if not self._h:
                raise IOError("cannot open %s" % path)
        else:
            self._f = open(path, "rb")
            magic = self._f.read(6)
            if magic != b"PYRIO\x00":
                raise IOError("bad recordio file (python-fallback format)")

    def __iter__(self) -> Iterator[bytes]:
        if self._lib is not None:
            n = ctypes.c_int(0)
            while True:
                ptr = self._lib.recordio_scanner_next(self._h, ctypes.byref(n))
                if not ptr:
                    if n.value == -1:
                        raise IOError("corrupt recordio chunk (CRC mismatch)")
                    return
                yield ctypes.string_at(ptr, n.value)
        else:
            while True:
                hdr = self._f.read(4)
                if len(hdr) < 4:
                    return
                ln = int.from_bytes(hdr, "little")
                yield self._f.read(ln)

    def close(self):
        if self._lib is not None:
            self._lib.recordio_scanner_close(self._h)
        else:
            self._f.close()


def parse_multislot(text: bytes, n_slots: int) -> Tuple[int, List[Tuple[np.ndarray, np.ndarray]]]:
    """Parse MultiSlot text (reference data_feed.cc format: per line, per
    slot ``<count> <v0> <v1> ...``).  Returns (n_lines, [(values, counts)]
    per slot)."""
    if isinstance(text, str):
        text = text.encode()
    lib = _build_and_load()
    if lib is not None:
        n_lines = ctypes.c_int(0)
        h = lib.multislot_parse(text, len(text), n_slots, ctypes.byref(n_lines))
        out = []
        try:
            for s in range(n_slots):
                nv = lib.multislot_slot_size(h, s)
                values = np.empty(nv, np.float32)
                counts = np.empty(n_lines.value, np.int32)
                if n_lines.value:
                    lib.multislot_copy_slot(
                        h, s,
                        values.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
                        counts.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
                    )
                out.append((values, counts))
        finally:
            lib.multislot_free(h)
        return n_lines.value, out
    return _parse_multislot_py(text, n_slots)


def _parse_multislot_py(text: bytes, n_slots: int):
    """Pure-Python fallback; malformed lines are skipped whole (matching
    the native parser's per-line rollback)."""
    if isinstance(text, str):
        text = text.encode()
    values = [[] for _ in range(n_slots)]
    counts = [[] for _ in range(n_slots)]
    n_lines = 0
    for line in text.decode().splitlines():
        toks = line.split()
        if not toks:
            continue
        pos = 0
        row = []
        ok = True
        for s in range(n_slots):
            if pos >= len(toks):
                ok = False
                break
            try:
                n = int(toks[pos])
                pos += 1
                if n < 0:
                    ok = False
                    break
                vals = [float(t) for t in toks[pos : pos + n]]
            except ValueError:
                ok = False
                break
            if len(vals) != n:
                ok = False
                break
            pos += n
            row.append((n, vals))
        if not ok:
            continue
        n_lines += 1
        for s, (n, vals) in enumerate(row):
            counts[s].append(n)
            values[s].extend(vals)
    return n_lines, [
        (np.asarray(values[s], np.float32), np.asarray(counts[s], np.int32))
        for s in range(n_slots)
    ]


# ---------------------------------------------------------------------------
# Native (C++) inference predictor — the Python-free deployment path
# (reference: inference/api/api_impl.h NativePaddlePredictor + the
# train/demo pure-C++ story).  predictor.cc parses __model__ JSON + .npy
# weights itself; this wrapper only builds/loads the .so and marshals
# buffers, so the same library is usable from any C program.
# ---------------------------------------------------------------------------
_pred_lib = None
_pred_tried = False


def _predictor_lib():
    global _pred_lib, _pred_tried
    if _pred_tried:
        return _pred_lib
    _pred_tried = True
    lib = _build_and_open("predictor.cc", "libpaddle_tpu_predictor")
    if lib is None:
        return None
    lib.ptp_predictor_create.restype = ctypes.c_void_p
    lib.ptp_predictor_create.argtypes = [ctypes.c_char_p]
    lib.ptp_predictor_error.restype = ctypes.c_char_p
    lib.ptp_predictor_error.argtypes = [ctypes.c_void_p]
    lib.ptp_predictor_set_input.restype = ctypes.c_int
    lib.ptp_predictor_set_input.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.POINTER(ctypes.c_float),
        ctypes.POINTER(ctypes.c_int64), ctypes.c_int,
    ]
    lib.ptp_predictor_set_input_i64.restype = ctypes.c_int
    lib.ptp_predictor_set_input_i64.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.POINTER(ctypes.c_int64),
        ctypes.POINTER(ctypes.c_int64), ctypes.c_int,
    ]
    lib.ptp_predictor_run.restype = ctypes.c_int
    lib.ptp_predictor_run.argtypes = [ctypes.c_void_p]
    lib.ptp_predictor_num_outputs.restype = ctypes.c_int
    lib.ptp_predictor_num_outputs.argtypes = [ctypes.c_void_p]
    lib.ptp_predictor_get_output.restype = ctypes.c_int64
    lib.ptp_predictor_get_output.argtypes = [
        ctypes.c_void_p, ctypes.c_int, ctypes.POINTER(ctypes.c_float),
        ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int),
        ctypes.c_int,
    ]
    lib.ptp_predictor_destroy.restype = None
    lib.ptp_predictor_destroy.argtypes = [ctypes.c_void_p]
    _pred_lib = lib
    return lib


class NativePredictor:
    """C++ inference over a saved inference model (no jax, no Python op
    kernels).  Covers the host inference op subset — see predictor.cc;
    unsupported ops raise with the supported list.  For full-op or TPU
    inference use ``paddle_tpu.inference.AnalysisPredictor``."""

    def __init__(self, model_dir: str):
        lib = _predictor_lib()
        if lib is None:
            raise RuntimeError(
                "native predictor unavailable (g++ build failed)"
            )
        self._lib = lib
        self._h = lib.ptp_predictor_create(str(model_dir).encode())
        err = lib.ptp_predictor_error(self._h)
        if err:
            msg = err.decode()
            lib.ptp_predictor_destroy(self._h)
            self._h = None
            raise RuntimeError("native predictor load: " + msg)

    def run(self, feeds: dict):
        lib = self._lib
        for name, arr in feeds.items():
            arr = np.ascontiguousarray(arr)
            shape = (ctypes.c_int64 * arr.ndim)(*arr.shape)
            if np.issubdtype(arr.dtype, np.integer):
                a64 = np.ascontiguousarray(arr, dtype=np.int64)
                lib.ptp_predictor_set_input_i64(
                    self._h, name.encode(),
                    a64.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
                    shape, arr.ndim,
                )
            else:
                a32 = np.ascontiguousarray(arr, dtype=np.float32)
                lib.ptp_predictor_set_input(
                    self._h, name.encode(),
                    a32.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
                    shape, arr.ndim,
                )
        if lib.ptp_predictor_run(self._h) != 0:
            raise RuntimeError(
                "native predictor run: "
                + lib.ptp_predictor_error(self._h).decode()
            )
        outs = []
        for i in range(lib.ptp_predictor_num_outputs(self._h)):
            shape = (ctypes.c_int64 * 16)()
            ndim = ctypes.c_int()
            n = lib.ptp_predictor_get_output(
                self._h, i, None, shape, ctypes.byref(ndim), 16)
            if n < 0:
                raise RuntimeError("native predictor: missing output %d" % i)
            buf = np.empty(int(n), np.float32)
            lib.ptp_predictor_get_output(
                self._h, i,
                buf.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
                shape, ctypes.byref(ndim), 16,
            )
            outs.append(buf.reshape([int(shape[d]) for d in range(ndim.value)]))
        return outs

    def __del__(self):
        if getattr(self, "_h", None) is not None:
            self._lib.ptp_predictor_destroy(self._h)


__all__.append("NativePredictor")
