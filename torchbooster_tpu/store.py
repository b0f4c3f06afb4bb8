"""Record store: mmap'd blob storage for datasets.

Capability parity with reference ``torchbooster/lmdb.py`` (105 LoC —
LMDBReader over liblmdb). The ``lmdb`` binding is not a dependency here;
instead records live in a **BoosterStore** file read by the native C++
library in ``native/booster_store.cpp`` (mmap + positional index — see
the format doc there), with a pure-python mmap fallback implementing the
identical format when no C++ toolchain is available.

API parity map (ref lmdb.py → here):
- ``LMDBReader(path)`` lazy open (ref :48-64)  → :class:`RecordReader`
  (opens lazily on first access — safe to construct before fork/spawn)
- ``length`` key protocol (ref :72-78)          → header record count
- ``reader[idx] -> bytes`` (ref :96-97)         → ``reader[idx]``
- context manager + iterator (ref :85-106)      → same
- (writer — the reference had none; datasets were prepared externally)
  → :class:`RecordWriter`
"""
from __future__ import annotations

import ctypes
import hashlib
import logging
import mmap
import os
import threading
import struct
import subprocess
from pathlib import Path
from typing import Iterator

_MAGIC = b"BSTORE1\x00"
_HEADER = struct.Struct("<8sQQ")   # magic, count, index_offset
_ENTRY = struct.Struct("<QQ")

_NATIVE_SOURCE = Path(__file__).resolve().parent.parent / "native" / "booster_store.cpp"
_NATIVE_LIB = _NATIVE_SOURCE.parent / "libbooster_store.so"
# sha256 of the source the library was built from, written beside it:
# the rebuild decision is made from content, because a copied tree
# keeps no useful mtime order and must never load a library built
# from another source
_NATIVE_STAMP = _NATIVE_LIB.with_name(_NATIVE_LIB.name + ".sha256")

_lib = None
_lib_tried = False


def _load_native() -> ctypes.CDLL | None:
    """Load (building on first use) the native store library. Returns
    None when unavailable — callers fall back to the python reader."""
    global _lib, _lib_tried
    if _lib_tried:
        return _lib
    _lib_tried = True
    try:
        want = hashlib.sha256(_NATIVE_SOURCE.read_bytes()).hexdigest()
        built = (_NATIVE_STAMP.read_text().strip()
                 if _NATIVE_LIB.exists() and _NATIVE_STAMP.exists()
                 else None)
        if built != want:
            # build aside and rename, so a concurrent loader (xdist
            # workers, loader processes) never maps a half-written file
            tmp = _NATIVE_LIB.with_name(f"{_NATIVE_LIB.name}.{os.getpid()}")
            subprocess.run(
                ["g++", "-O3", "-shared", "-fPIC", "-o", str(tmp),
                 str(_NATIVE_SOURCE)],
                check=True, capture_output=True, timeout=120)
            os.replace(tmp, _NATIVE_LIB)
            _NATIVE_STAMP.write_text(want + "\n")
        lib = ctypes.CDLL(str(_NATIVE_LIB))
        lib.bs_open.restype = ctypes.c_void_p
        lib.bs_open.argtypes = [ctypes.c_char_p]
        lib.bs_count.restype = ctypes.c_int64
        lib.bs_count.argtypes = [ctypes.c_void_p]
        lib.bs_get.restype = ctypes.c_int
        lib.bs_get.argtypes = [
            ctypes.c_void_p, ctypes.c_uint64,
            ctypes.POINTER(ctypes.POINTER(ctypes.c_uint8)),
            ctypes.POINTER(ctypes.c_uint64)]
        lib.bs_get_batch.restype = ctypes.c_int64
        lib.bs_get_batch.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_uint64),
            ctypes.c_uint64, ctypes.c_char_p, ctypes.c_uint64,
            ctypes.POINTER(ctypes.c_uint64)]
        lib.bs_close.argtypes = [ctypes.c_void_p]
        lib.bs_writer_open.restype = ctypes.c_void_p
        lib.bs_writer_open.argtypes = [ctypes.c_char_p]
        lib.bs_writer_append.restype = ctypes.c_int
        lib.bs_writer_append.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_uint64]
        lib.bs_writer_close.restype = ctypes.c_int
        lib.bs_writer_close.argtypes = [ctypes.c_void_p]
        lib.bs_error.restype = ctypes.c_char_p
        _lib = lib
    except (subprocess.SubprocessError, OSError) as error:
        logging.warning("native BoosterStore unavailable (%s); using "
                        "python mmap fallback", error)
    return _lib


class RecordReader:
    """Read-only record access (ref LMBDReader lmdb.py:13-106). Opens
    lazily on first use (ref :48-64 — lazy open is what makes the object
    safe to hand to dataloader workers before fork).

    Two equivalent readers over the same file format:

    - ``native=False`` (default): python ``mmap`` + ``struct`` — the
      fast path *from Python*. Slicing an mmap is a single C memcpy;
      measured ~0.8µs/record vs ~3.5µs/record through the ctypes FFI
      (per-call conversion overhead dominates for small records).
    - ``native=True``: the C++ library — the format's reference
      implementation, with hard bounds checks and ``madvise``; the
      right entry point for non-Python consumers and large records.
    """

    def __init__(self, path: str | Path, native: bool = False):
        self.path = Path(path)
        self._want_native = native
        self._handle = None
        self._mmap: mmap.mmap | None = None
        self._file = None
        self._count: int | None = None
        self._index_offset = 0
        self._native = False
        self._open_lock = threading.Lock()

    # -- lifecycle ---------------------------------------------------

    def open(self) -> "RecordReader":
        # loader worker threads race the first access (num_workers>0)
        with self._open_lock:
            return self._open_locked()

    def _open_locked(self) -> "RecordReader":
        if self._count is not None:
            return self
        lib = _load_native() if self._want_native else None
        if lib is not None:
            handle = lib.bs_open(str(self.path).encode())
            if not handle:
                raise OSError(
                    f"cannot open {self.path}: {lib.bs_error().decode()}")
            self._handle = handle
            self._count = int(lib.bs_count(handle))
            self._native = True
            return self
        # python mmap reader (identical format)
        try:
            self._file = open(self.path, "rb")
            self._mmap = mmap.mmap(self._file.fileno(), 0,
                                   access=mmap.ACCESS_READ)
        except (OSError, ValueError) as error:
            raise OSError(f"cannot open {self.path}: {error}") from error
        if len(self._mmap) < _HEADER.size:
            raise OSError(f"{self.path} is not a BoosterStore file (too small)")
        magic, count, index_offset = _HEADER.unpack_from(self._mmap, 0)
        if magic != _MAGIC:
            raise OSError(f"{self.path} is not a BoosterStore file")
        if index_offset > len(self._mmap) or \
                count > (len(self._mmap) - index_offset) // 16:
            raise OSError(f"{self.path}: corrupt header, index out of bounds")
        self._count = count
        self._index_offset = index_offset
        return self

    def close(self) -> None:
        if self._native and self._handle is not None:
            _load_native().bs_close(self._handle)
            self._handle = None
        if self._mmap is not None:
            self._mmap.close()
            self._file.close()
            self._mmap = None
        self._count = None

    def __enter__(self) -> "RecordReader":
        return self.open()

    def __exit__(self, *exc) -> None:
        self.close()

    # -- access ------------------------------------------------------

    def __len__(self) -> int:
        self.open()
        return self._count

    def get(self, index: int) -> bytes:
        """ref lmdb.py:72-83 (key = str(index) there; positional here)."""
        self.open()
        if not 0 <= index < self._count:
            raise IndexError(f"record {index} out of range [0, {self._count})")
        if self._native:
            lib = _load_native()
            data = ctypes.POINTER(ctypes.c_uint8)()
            size = ctypes.c_uint64()
            if lib.bs_get(self._handle, index, ctypes.byref(data),
                          ctypes.byref(size)) != 0:
                raise OSError(f"read failed: {lib.bs_error().decode()}")
            return ctypes.string_at(data, size.value)
        offset, size = _ENTRY.unpack_from(
            self._mmap, self._index_offset + 16 * index)
        if offset > len(self._mmap) or size > len(self._mmap) - offset:
            raise OSError(f"{self.path}: corrupt index entry {index}")
        return bytes(self._mmap[offset:offset + size])

    def __getitem__(self, index: int) -> bytes:
        return self.get(index)

    def get_batch(self, indices) -> list[bytes]:
        """Gather many records in one pass (the torch ``__getitems__``
        analogue at the storage layer). Native path: two FFI calls per
        batch (size pass + one C++ memcpy gather) instead of one call
        per record; python path: direct mmap slices."""
        self.open()
        n = len(indices)
        if n == 0:
            return []
        if self._native:
            lib = _load_native()
            idx_arr = (ctypes.c_uint64 * n)(*[int(i) for i in indices])
            sizes = (ctypes.c_uint64 * n)()
            total = lib.bs_get_batch(self._handle, idx_arr, n, None, 0, sizes)
            if total < 0:
                raise OSError(f"batch read failed: {lib.bs_error().decode()}")
            buffer = (ctypes.c_char * total)()
            written = lib.bs_get_batch(self._handle, idx_arr, n, buffer,
                                       total, sizes)
            if written != total:
                raise OSError(f"batch read failed: {lib.bs_error().decode()}")
            view = memoryview(buffer)
            out, cursor = [], 0
            for i in range(n):
                out.append(bytes(view[cursor:cursor + sizes[i]]))
                cursor += sizes[i]
            return out
        return [self.get(int(i)) for i in indices]

    def __iter__(self) -> Iterator[bytes]:
        for index in range(len(self)):
            yield self.get(index)


class RecordWriter:
    """Sequential store builder (no reference analogue — the reference's
    LMDB files were prepared out-of-band; :meth:`BaseDataset.prepare`
    uses this, ref dataset.py:49-56)."""

    def __init__(self, path: str | Path):
        self.path = Path(path)
        self.path.parent.mkdir(parents=True, exist_ok=True)
        self._count = 0
        lib = _load_native()
        if lib is not None:
            self._handle = lib.bs_writer_open(str(self.path).encode())
            if not self._handle:
                raise OSError(
                    f"cannot create {self.path}: {lib.bs_error().decode()}")
            self._native = True
        else:
            self._file = open(self.path, "wb")
            self._file.write(_HEADER.pack(_MAGIC, 0, 0))
            self._index: list[tuple[int, int]] = []
            self._cursor = _HEADER.size
            self._native = False

    def append(self, data: bytes) -> int:
        """Append one record; returns its index."""
        if self._native:
            lib = _load_native()
            if lib.bs_writer_append(self._handle, data, len(data)) != 0:
                raise OSError(f"append failed: {lib.bs_error().decode()}")
        else:
            self._file.write(data)
            self._index.append((self._cursor, len(data)))
            self._cursor += len(data)
        self._count += 1
        return self._count - 1

    def close(self) -> None:
        if self._native:
            lib = _load_native()
            if self._handle is not None:
                handle, self._handle = self._handle, None
                # bs_writer_close frees the Writer on every path — clear
                # the handle BEFORE raising so a second close can never
                # pass freed memory back into the library
                if lib.bs_writer_close(handle) != 0:
                    raise OSError(
                        f"finalize failed: {lib.bs_error().decode()}")
        else:
            if self._file is None:
                return
            index_offset = self._cursor
            for offset, size in self._index:
                self._file.write(_ENTRY.pack(offset, size))
            self._file.seek(0)
            self._file.write(_HEADER.pack(_MAGIC, self._count, index_offset))
            self._file.close()
            self._file = None

    def abort(self) -> None:
        """Discard the store: release resources and delete the partial
        file (never leaves a valid-looking header behind)."""
        if self._native:
            lib = _load_native()
            if self._handle is not None:
                handle, self._handle = self._handle, None
                lib.bs_writer_close(handle)
        else:
            if self._file is not None:
                self._file.close()
                self._file = None
        self.path.unlink(missing_ok=True)

    def __enter__(self) -> "RecordWriter":
        return self

    def __exit__(self, exc_type, *exc) -> None:
        # a crashed with-body must not finalize a valid-looking store:
        # a half-built file would be indistinguishable from a complete
        # one to the store-exists checks downstream
        if exc_type is not None:
            self.abort()
        else:
            self.close()

    @classmethod
    def from_lmdb(cls, src: str | Path, dst: str | Path) -> int:
        """Migrate a reference-era LMDB corpus into a BoosterStore file.

        When the database follows the reference's size-key convention
        (``b"length"`` holding the count, records under ``str(i)`` keys
        — ref lmdb.py:63, dataset.py:58-66), records migrate in index
        order and ``b"length"`` itself is dropped (BoosterStore carries
        the count in its header). Otherwise every (key, value) pair
        migrates in key order. Needs no native dependency: uses the
        ``lmdb`` package when installed, else the bundled pure-python
        parser (:mod:`torchbooster_tpu.lmdb_compat`). Returns the
        record count.
        """
        from torchbooster_tpu.lmdb_compat import LMDBView

        with LMDBView(src) as view, cls(dst) as writer:
            length = view.length()
            if length is not None:
                for i in range(length):
                    value = view.get(str(i).encode())
                    if value is None:
                        raise KeyError(
                            f"{src}: declares length={length} but key "
                            f"{i!r} is missing")
                    writer.append(value)
            else:
                for _, value in view.items():
                    writer.append(value)
            return writer._count


# Reference-parity alias (ref lmdb.py class name, [sic] LMBDReader at
# lmdb.py:13 — the reference's own typo'd spelling is NOT carried over;
# the sensible name is provided for discoverability).
LMDBReader = RecordReader

__all__ = ["LMDBReader", "RecordReader", "RecordWriter"]
