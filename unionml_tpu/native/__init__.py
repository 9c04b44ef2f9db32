"""Native runtime components (C++), consumed via ctypes.

The shared library builds lazily on first use with the system toolchain (g++); when no
compiler is available (or the checkout is read-only) the callers fall back to the
pure-Python path, so the framework never hard-depends on the native build.
"""

import ctypes
import hashlib
import os
import subprocess
import threading
from pathlib import Path
from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from unionml_tpu._logging import logger

_SOURCES = (
    Path(__file__).parent / "prefetch.cpp",
    Path(__file__).parent / "pack.cpp",
)
_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_build_failed = False

#: worker-side dtype conversions (mirrors the Conv enum in prefetch.cpp):
#: source dtype -> (code, destination numpy dtype)
_CONV_CODES = {
    "float64->float32": 1,
    "int64->int32": 2,
    "float32->bfloat16": 3,
}


def _library_path() -> Path:
    """Where the library built from the CURRENT sources lives: under the
    checkout (``native/_build/``, git-ignored), named by a hash of the source
    bytes. The name is the freshness check — a library built from other
    sources, by another tree or an older version, has another name and is
    never loaded, whatever its mtime."""
    digest = hashlib.sha256()
    for src in _SOURCES:
        digest.update(src.read_bytes())
    return Path(__file__).parent / "_build" / f"libunionml_native-{digest.hexdigest()[:16]}.so"


def _compile(lib_path: Path) -> None:
    """Compile every native source into ``lib_path`` with the system toolchain."""
    lib_path.parent.mkdir(parents=True, exist_ok=True)
    # build beside the target, then rename: the name alone says "complete and
    # current", so a half-written file must never carry it
    partial = lib_path.with_name(f"{lib_path.name}.{os.getpid()}.partial")
    try:
        subprocess.run(
            [
                "g++",
                "-O3",
                "-shared",
                "-fPIC",
                "-pthread",
                "-std=c++17",
                *[str(src) for src in _SOURCES],
                "-o",
                str(partial),
            ],
            check=True,
            capture_output=True,
        )
        os.replace(partial, lib_path)
    finally:
        partial.unlink(missing_ok=True)
    logger.info("Built native prefetcher -> %s", lib_path)


def load_native_library() -> Optional[ctypes.CDLL]:
    """Build (once) and load the native library; None when unavailable."""
    global _lib, _build_failed
    with _lock:
        if _lib is not None or _build_failed:
            return _lib
        try:
            lib_path = _library_path()
            if not lib_path.exists():
                # graftlint: disable=lock-order -- the lock intentionally serializes the ONE-TIME g++ build: concurrent first callers must wait for the compile rather than race it; every later call returns the cached handle without blocking
                _compile(lib_path)
            lib = ctypes.CDLL(str(lib_path))
            _bind_symbols(lib)
        except (subprocess.CalledProcessError, OSError, AttributeError) as exc:
            detail = getattr(exc, "stderr", b"")
            logger.warning(
                "Native prefetcher unavailable (%s %s); falling back to Python batching.",
                exc,
                detail.decode(errors="replace")[:500] if isinstance(detail, bytes) else detail,
            )
            _build_failed = True
            return None
        _lib = lib
        return _lib


def _bind_symbols(lib: ctypes.CDLL) -> None:
    """Declare every C-ABI signature; AttributeError if any symbol is absent."""
    lib.upf_create.restype = ctypes.c_void_p
    lib.upf_create.argtypes = [
        ctypes.POINTER(ctypes.c_void_p),
        ctypes.POINTER(ctypes.c_long),
        ctypes.POINTER(ctypes.c_long),
        ctypes.POINTER(ctypes.c_long),
        ctypes.c_long,
        ctypes.c_long,
    ]
    lib.upf_start.argtypes = [
        ctypes.c_void_p,
        ctypes.POINTER(ctypes.c_long),
        ctypes.c_long,
        ctypes.c_long,
        ctypes.c_long,
        ctypes.c_long,
        ctypes.POINTER(ctypes.c_void_p),
    ]
    lib.upf_next.restype = ctypes.c_long
    lib.upf_next.argtypes = [ctypes.c_void_p]
    lib.upf_release.argtypes = [ctypes.c_void_p, ctypes.c_long]
    lib.upf_destroy.argtypes = [ctypes.c_void_p]
    lib.upk_pack.restype = ctypes.c_longlong
    lib.upk_pack.argtypes = [
        ctypes.POINTER(ctypes.c_int32),
        ctypes.POINTER(ctypes.c_int64),
        ctypes.c_longlong,
        ctypes.c_longlong,
        ctypes.c_int32,
        ctypes.c_longlong,
        ctypes.POINTER(ctypes.c_int32),
        ctypes.POINTER(ctypes.c_int32),
        ctypes.POINTER(ctypes.c_int32),
    ]
    lib.upk_count_rows.restype = ctypes.c_longlong
    lib.upk_count_rows.argtypes = [
        ctypes.POINTER(ctypes.c_int64),
        ctypes.c_longlong,
        ctypes.c_longlong,
        ctypes.c_longlong,
    ]


def pack_sequences_native(
    flat_tokens: np.ndarray,
    lengths: np.ndarray,
    seq_len: int,
    pad_id: int,
    max_segments_per_row: int,
) -> Optional[Dict[str, np.ndarray]]:
    """First-fit packing through the native library; None when it is unavailable.

    Inputs are pre-normalized by :func:`unionml_tpu.ops.packing.pack_sequences`
    (empties filtered, overlong sequences truncated, tokens concatenated); the
    wrapper re-checks that ``lengths`` sums to ``flat_tokens.size`` (the C side
    walks the buffer unchecked) and runs the two-pass protocol: count rows,
    allocate exact outputs, pack. Output arrays are byte-identical to the
    Python path's.
    """
    lib = load_native_library()
    if lib is None:
        return None
    flat_tokens = np.ascontiguousarray(flat_tokens, dtype=np.int32)
    lengths = np.ascontiguousarray(lengths, dtype=np.int64)
    if int(lengths.sum()) != flat_tokens.size:
        # the C side walks flat_tokens by the cumulative lengths with no bounds
        # check of its own; a short buffer would be an out-of-bounds READ in
        # upk_pack, so reject the call here and let the Python path (which
        # indexes safely) surface whatever is wrong with the inputs
        logger.warning(
            "Native packer input mismatch: lengths sum to %d but flat_tokens has %d "
            "tokens; using the Python path.",
            int(lengths.sum()),
            flat_tokens.size,
        )
        return None
    n_seqs = int(lengths.size)
    lengths_ptr = lengths.ctypes.data_as(ctypes.POINTER(ctypes.c_int64))
    # two-pass protocol: count rows first, allocate EXACT outputs — a
    # worst-case (n_seqs, seq_len) x3 allocation is multi-GB at the corpus
    # scales this packer exists for. The count runs the identical first-fit
    # loop, so upk_pack fills exactly n_rows rows.
    n_rows = lib.upk_count_rows(lengths_ptr, n_seqs, seq_len, max_segments_per_row)
    if n_rows < 0:
        logger.warning("Native packer rejected inputs (rc=%d); using the Python path.", n_rows)
        return None
    input_ids = np.empty((n_rows, seq_len), dtype=np.int32)
    segment_ids = np.empty((n_rows, seq_len), dtype=np.int32)
    positions = np.empty((n_rows, seq_len), dtype=np.int32)
    as_i32 = lambda a: a.ctypes.data_as(ctypes.POINTER(ctypes.c_int32))
    packed_rows = lib.upk_pack(
        as_i32(flat_tokens),
        lengths_ptr,
        n_seqs,
        seq_len,
        pad_id,
        max_segments_per_row,
        as_i32(input_ids),
        as_i32(segment_ids),
        as_i32(positions),
    )
    if packed_rows != n_rows:  # defensive: the two passes must agree exactly
        logger.warning(
            "Native packer row-count mismatch (%d vs %d); using the Python path.",
            packed_rows, n_rows,
        )
        return None
    return {
        "input_ids": input_ids,
        "segment_ids": segment_ids,
        "positions": positions,
    }


def native_available() -> bool:
    return load_native_library() is not None


def _bfloat16_dtype():
    import ml_dtypes

    return np.dtype(ml_dtypes.bfloat16)


def _resolve_conversion(array: np.ndarray, target: Optional[str]) -> Tuple[int, np.dtype]:
    """(conv code, destination dtype) for one source array."""
    if target is None:
        return 0, array.dtype
    target_dtype = _bfloat16_dtype() if target == "bfloat16" else np.dtype(target)
    if target_dtype == array.dtype:
        return 0, array.dtype  # no-op conversion request: plain gather
    key = f"{array.dtype.name}->{target}"
    code = _CONV_CODES.get(key)
    if code is None:
        raise ValueError(
            f"Unsupported native conversion {key!r}; supported: {sorted(_CONV_CODES)}"
        )
    return code, target_dtype


class PrefetchLoader:
    """Iterate dict batches gathered by the native threaded prefetcher.

    Wraps a mapping of name -> contiguous host array; each epoch yields dict batches
    in shuffled order with gathering overlapped against the consumer's compute.

    Hot-path design:

    - Slot buffers are numpy arrays OWNED BY PYTHON; the C++ workers gather straight
      into them, so ``copy=False`` consumers hand the batch to ``jax.device_put``
      with zero additional host copies. The slot recycles only after the generator
      resumes — block on the transfer before advancing (``fit`` does).
    - ``convert={"name": "float32" | "int32" | "bfloat16"}`` runs the dtype
      conversion inside the worker threads (f64->f32, i64->i32, f32->bf16 with
      round-to-nearest-even) — the Python side never pays element-wise conversion.

    Falls back to pure-Python batching when the native library can't build.
    """

    def __init__(
        self,
        data: Dict[str, np.ndarray],
        batch_size: int,
        *,
        n_slots: int = 4,
        n_threads: int = 2,
        drop_remainder: bool = True,
        convert: Optional[Dict[str, str]] = None,
    ):
        self._keys = list(data)
        self._arrays = [np.ascontiguousarray(np.asarray(data[k])) for k in self._keys]
        n_rows = {a.shape[0] for a in self._arrays}
        if len(n_rows) != 1:
            raise ValueError(f"All arrays must share the leading dimension; got {n_rows}")
        self.n_rows = n_rows.pop()
        self.batch_size = batch_size
        self.n_slots = n_slots
        self.n_threads = n_threads
        self.drop_remainder = drop_remainder

        convert = convert or {}
        unknown = set(convert) - set(self._keys)
        if unknown:
            raise ValueError(f"convert refers to unknown arrays: {sorted(unknown)}")
        self._conv_codes: List[int] = []
        self._dst_dtypes: List[np.dtype] = []
        for key, array in zip(self._keys, self._arrays):
            code, dst = _resolve_conversion(array, convert.get(key))
            self._conv_codes.append(code)
            self._dst_dtypes.append(dst)

        self._lib = load_native_library()
        self._handle = None
        self._slot_arrays: List[List[np.ndarray]] = []
        self._slot_ptr_table = None
        if self._lib is not None:
            n = len(self._arrays)
            sources = (ctypes.c_void_p * n)(
                *[a.ctypes.data_as(ctypes.c_void_p).value for a in self._arrays]
            )
            row_bytes = (ctypes.c_long * n)(*[a.strides[0] for a in self._arrays])
            dst_row_bytes = (ctypes.c_long * n)(*self._dst_row_bytes())
            conv_codes = (ctypes.c_long * n)(*self._conv_codes)
            self._handle = self._lib.upf_create(
                sources, row_bytes, conv_codes, dst_row_bytes, n, self.n_rows
            )
            self._allocate_slots()

    def _dst_row_bytes(self) -> List[int]:
        out = []
        for array, dst in zip(self._arrays, self._dst_dtypes):
            row_elems = int(np.prod(array.shape[1:], dtype=np.int64)) if array.ndim > 1 else 1
            out.append(row_elems * dst.itemsize)
        return out

    def _allocate_slots(self) -> None:
        """Python-owned destination buffers: [n_slots][n_arrays] numpy arrays."""
        self._slot_arrays = []
        pointers = []
        for _ in range(self.n_slots):
            slot = []
            for array, dst in zip(self._arrays, self._dst_dtypes):
                buf = np.empty((self.batch_size,) + array.shape[1:], dtype=dst)
                slot.append(buf)
                pointers.append(buf.ctypes.data_as(ctypes.c_void_p).value)
            self._slot_arrays.append(slot)
        self._slot_ptr_table = (ctypes.c_void_p * len(pointers))(*pointers)

    @property
    def uses_native(self) -> bool:
        return self._handle is not None

    def _python_batch(self, idx: np.ndarray) -> Dict[str, np.ndarray]:
        out = {}
        for key, array, dst in zip(self._keys, self._arrays, self._dst_dtypes):
            gathered = array[idx]
            out[key] = gathered.astype(dst) if dst != array.dtype else gathered
        return out

    def epoch(
        self,
        rng: Optional[np.random.Generator] = None,
        copy: bool = True,
        defer_release: bool = False,
    ) -> Iterator[Any]:
        """Yield one epoch of dict batches in shuffled order.

        ``copy=True`` (default) yields loader-independent arrays: safe for any
        consumer, including fully-async device transfers. ``copy=False`` yields the
        python-owned slot arrays themselves — ZERO host copies after the worker
        gather — which recycle after the generator resumes: the consumer must finish
        reading (e.g. ``jax.block_until_ready`` on the device transfer) inside the loop body.

        ``defer_release=True`` yields ``(views, release)`` pairs instead: the slot
        is recycled only when ``release()`` is called, so a consumer may hold a
        batch (e.g. an in-flight device transfer) while pulling the next one —
        the transfer-overlap lookahead ``fit()`` uses. Releases should happen in
        yield order; holding more than ``n_slots - 1`` unreleased batches stalls
        the gather workers.
        """
        indices = np.arange(self.n_rows, dtype=np.int64) if rng is None else rng.permutation(self.n_rows).astype(np.int64)
        # the native path only ever gathers FULL batches (its buffers are fixed-size);
        # a ragged tail is yielded via the python gather below, preserving true-batch
        # semantics with drop_remainder=False
        n_full = self.n_rows // self.batch_size
        remainder = self.n_rows - n_full * self.batch_size

        def emit(views, release=None):
            # python-gathered batches are fresh arrays: release is a no-op
            return (views, release or (lambda: None)) if defer_release else views

        def tail_batches():
            if not self.drop_remainder and remainder:
                yield emit(self._python_batch(indices[n_full * self.batch_size :]))
            elif n_full == 0:
                # degenerate tiny datasets always yield their one true batch
                yield emit(self._python_batch(indices))

        if self._handle is None or n_full == 0:
            for b in range(n_full):
                yield emit(self._python_batch(indices[b * self.batch_size : (b + 1) * self.batch_size]))
            yield from tail_batches()
            return

        n_batches = n_full
        indices_c = np.ascontiguousarray(indices[: n_batches * self.batch_size])
        self._lib.upf_start(
            self._handle,
            indices_c.ctypes.data_as(ctypes.POINTER(ctypes.c_long)),
            n_batches,
            self.batch_size,
            self.n_slots,
            self.n_threads,
            self._slot_ptr_table,
        )
        try:
            while True:
                batch = self._lib.upf_next(self._handle)
                if batch < 0:
                    break
                slot = self._slot_arrays[batch % self.n_slots]
                views = {
                    key: (np.array(buf) if copy else buf)
                    for key, buf in zip(self._keys, slot)
                }
                if defer_release:
                    released = [False]

                    def release(b=batch, flag=released):
                        if not flag[0] and self._handle is not None:
                            flag[0] = True
                            self._lib.upf_release(self._handle, b)

                    yield views, release
                else:
                    yield views
                    self._lib.upf_release(self._handle, batch)
            yield from tail_batches()
        finally:
            del indices_c

    def close(self) -> None:
        if self._handle is not None and self._lib is not None:
            self._lib.upf_destroy(self._handle)
            self._handle = None

    def __del__(self):  # pragma: no cover - best effort
        try:
            self.close()
        except Exception:  # best-effort-release shape: recognized by the lint
            pass
