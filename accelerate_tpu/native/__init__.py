"""Native runtime components (C++), with pure-Python fallbacks.

The reference's host-side data path rides torch's native machinery (worker
processes, pinned-memory copies — reference `data_loader.py:550-573` prefetch and
`MpDeviceLoaderWrapper`'s background loader threads). This package provides the
TPU-native equivalent as an in-tree C++ component: `prefetch_ring.cpp`, a
background gather-copy ring of 64-byte-aligned host staging buffers driven from
`HostPrefetcher` (host_prefetcher.py) and `DataLoaderShard(prefetch=...)`.

The shared library builds on first use with g++ (cached next to the source,
under a name derived from the source's content);
every consumer degrades gracefully to the Python path when no toolchain is
available, so the framework never hard-depends on the native build.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from pathlib import Path

_HERE = Path(__file__).resolve().parent
_SRC = _HERE / "prefetch_ring.cpp"
_BUILD_LOCK = threading.Lock()
_LOAD_FAILURE: str | None = None
_lib: ctypes.CDLL | None = None


def _lib_path() -> Path:
    """The binary is named after the source it was built from: a library left
    in the tree by another checkout, or copied with fresher mtimes than the
    source, can then never be loaded in place of this source's own build."""
    digest = hashlib.sha256(_SRC.read_bytes()).hexdigest()[:12]
    return _HERE / f"libprefetch_ring.{digest}.so"


def _build(lib_path: Path) -> bool:
    # compile to a process-unique temp path, then rename atomically: concurrent
    # processes (multi-host launch, parallel tests) must never dlopen a
    # partially-written .so
    tmp = lib_path.with_suffix(f".so.tmp{os.getpid()}")
    cmd = [
        "g++", "-O3", "-shared", "-fPIC", "-std=c++17", "-pthread",
        str(_SRC), "-o", str(tmp),
    ]
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True, timeout=120)
    except (OSError, subprocess.TimeoutExpired) as e:
        globals()["_LOAD_FAILURE"] = f"g++ unavailable: {e}"
        return False
    if proc.returncode != 0:
        globals()["_LOAD_FAILURE"] = f"native build failed: {proc.stderr[-500:]}"
        tmp.unlink(missing_ok=True)
        return False
    os.replace(tmp, lib_path)
    return True


def _load() -> ctypes.CDLL | None:
    global _lib, _LOAD_FAILURE
    if _lib is not None:
        return _lib
    if os.environ.get("ACCELERATE_TPU_DISABLE_NATIVE", "") not in ("", "0", "false"):
        _LOAD_FAILURE = "disabled via ACCELERATE_TPU_DISABLE_NATIVE"
        return None
    with _BUILD_LOCK:
        if _lib is not None:
            return _lib
        if _LOAD_FAILURE is not None:
            return None
        lib_path = _lib_path()
        if not lib_path.exists() and not _build(lib_path):
            return None
        try:
            lib = ctypes.CDLL(str(lib_path))
        except OSError as e:
            _LOAD_FAILURE = f"dlopen failed: {e}"
            return None
        lib.ring_create.restype = ctypes.c_void_p
        lib.ring_create.argtypes = [ctypes.c_int, ctypes.c_size_t]
        lib.ring_push_batch.restype = ctypes.c_long
        lib.ring_push_batch.argtypes = [
            ctypes.c_void_p,
            ctypes.POINTER(ctypes.c_void_p),
            ctypes.POINTER(ctypes.c_size_t),
            ctypes.c_int,
        ]
        lib.ring_pop.restype = ctypes.c_void_p
        lib.ring_pop.argtypes = [
            ctypes.c_void_p,
            ctypes.POINTER(ctypes.c_size_t),
            ctypes.POINTER(ctypes.c_long),
        ]
        lib.ring_release.argtypes = [ctypes.c_void_p]
        lib.ring_stop.argtypes = [ctypes.c_void_p]
        lib.ring_completed.restype = ctypes.c_long
        lib.ring_completed.argtypes = [ctypes.c_void_p]
        lib.ring_destroy.argtypes = [ctypes.c_void_p]
        lib.ring_alignment.restype = ctypes.c_size_t
        _lib = lib
        return _lib


def is_native_available() -> bool:
    """True when the C++ prefetch ring built (or was already built) and loads."""
    return _load() is not None


def native_unavailable_reason() -> str | None:
    _load()
    return _LOAD_FAILURE


class PrefetchRing:
    """ctypes wrapper over one native ring (see prefetch_ring.cpp).

    ``push(arrays)`` enqueues an async gather-copy of the numpy arrays into one
    aligned slot and returns a job id; the caller must keep the sources alive
    until ``completed() > job_id``. ``pop()`` blocks for the oldest ready slot
    and returns 64-byte-aligned numpy views into it (zero-copy); ``release()``
    recycles the oldest popped slot once its views are dead.
    """

    def __init__(self, n_slots: int, slot_bytes: int):
        import numpy as np  # local: keep module import light

        self._np = np
        self._inflight: dict = {}
        self._inflight_mu = threading.Lock()
        lib = _load()
        if lib is None:
            raise RuntimeError(f"native prefetch ring unavailable: {_LOAD_FAILURE}")
        self._lib = lib
        self._align = int(lib.ring_alignment())
        self._h = lib.ring_create(ctypes.c_int(n_slots), ctypes.c_size_t(slot_bytes))
        if not self._h:
            raise MemoryError("ring_create failed")
        self.slot_bytes = slot_bytes

    def push(self, arrays) -> int:
        np = self._np
        arrs = [np.ascontiguousarray(a) for a in arrays]
        n = len(arrs)
        srcs = (ctypes.c_void_p * n)(
            *[a.ctypes.data_as(ctypes.c_void_p).value for a in arrs]
        )
        sizes = (ctypes.c_size_t * n)(*[a.nbytes for a in arrs])
        job = int(self._lib.ring_push_batch(self._h, srcs, sizes, ctypes.c_int(n)))
        if job == -1:
            raise ValueError(
                f"batch of {sum(a.nbytes for a in arrs)}B (aligned) exceeds slot "
                f"capacity {self.slot_bytes}B"
            )
        if job < 0:
            raise RuntimeError("ring is shutting down")
        # the ctypes arrays and arrs must outlive the async copy; the lock is
        # needed because push runs on the producer thread and _gc_inflight on
        # the consumer thread
        with self._inflight_mu:
            self._inflight[job] = (arrs, srcs, sizes)
        return job

    def _gc_inflight(self):
        done = int(self._lib.ring_completed(self._h))
        with self._inflight_mu:
            for job in [j for j in self._inflight if j < done]:
                del self._inflight[job]

    def pop(self, specs, copy: bool = True):
        """Blocking pop; ``specs`` is [(shape, dtype), ...] matching the pushed
        arrays. Returns (arrays, job_id).

        ``copy=True`` (default) returns owning arrays — always safe. With
        ``copy=False`` the arrays are zero-copy views into the slot, valid ONLY
        until the slot's `release()` (and never after `close()`); use it only
        when the consumer finishes with the data before releasing.
        """
        np = self._np
        nbytes = ctypes.c_size_t(0)
        job_id = ctypes.c_long(0)
        base = self._lib.ring_pop(self._h, ctypes.byref(nbytes), ctypes.byref(job_id))
        if not base:
            raise RuntimeError("ring is shutting down")
        self._gc_inflight()
        views = []
        off = 0
        for shape, dtype in specs:
            dt = np.dtype(dtype)
            count = int(np.prod(shape)) if len(shape) else 1
            seg = count * dt.itemsize
            buf = (ctypes.c_char * seg).from_address(base + off)
            v = np.frombuffer(buf, dtype=dt).reshape(shape)
            views.append(v.copy() if copy else v)
            off += -(-seg // self._align) * self._align
        return views, int(job_id.value)

    def release(self) -> None:
        self._lib.ring_release(self._h)

    def completed(self) -> int:
        return int(self._lib.ring_completed(self._h))

    def stop(self) -> None:
        """Unblock every thread waiting inside a ring call (push/pop return
        'shutting down'); the ring stays allocated until close()."""
        if getattr(self, "_h", None):
            self._lib.ring_stop(self._h)

    def close(self) -> None:
        if getattr(self, "_h", None):
            self._lib.ring_destroy(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


from .host_prefetcher import HostPrefetcher  # noqa: E402  (uses _load lazily)
