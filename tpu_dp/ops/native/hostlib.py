"""Lazy g++ build + ctypes bindings for the native host library."""

from __future__ import annotations

import ctypes
import os
import socket as _socket
import subprocess
import threading
from pathlib import Path

import numpy as np

_SRC = Path(__file__).with_name("hostring.cpp")
_lock = threading.Lock()
_lib: ctypes.CDLL | None = None
_build_failed = False


def _build(out: Path) -> bool:
    """Compile hostring.cpp to ``out`` (atomic: tmp + rename, so concurrent
    importers — e.g. spawned test workers — never load a half-written .so)."""
    try:
        out.parent.mkdir(parents=True, exist_ok=True)
        tmp = out.with_name(f"{out.name}.tmp.{os.getpid()}")
        cmd = [
            "g++", "-O2", "-fPIC", "-shared", "-std=c++17", "-pthread",
            str(_SRC), "-o", str(tmp),
        ]
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.replace(tmp, out)
        return True
    except (subprocess.SubprocessError, FileNotFoundError, OSError):
        return False


def _cached_lib_path() -> Path:
    """Content-addressed build location outside the source tree.

    Keyed on the source hash: editing hostring.cpp gets a fresh build
    without mtime games, and the checkout may be read-only.
    """
    import hashlib

    digest = hashlib.sha256(_SRC.read_bytes()).hexdigest()[:16]
    root = Path(
        os.environ.get("TPU_DP_CACHE_DIR")
        or os.environ.get("XDG_CACHE_HOME")
        or Path.home() / ".cache"
    )
    return root / "tpu_dp" / f"libtpudp_host-{digest}.so"


def _try_load(path: Path) -> ctypes.CDLL | None:
    try:
        return ctypes.CDLL(str(path))
    except OSError:
        return None


def _get() -> ctypes.CDLL | None:
    global _lib, _build_failed
    with _lock:
        if _lib is not None:
            return _lib
        if _build_failed:
            return None
        # Built from hostring.cpp into the digest-named cache path, and
        # from nothing else: a library lying in the source tree is not
        # among the files git commits.
        cached = _cached_lib_path()
        lib = _try_load(cached) if cached.exists() else None
        if lib is None:
            # Cache missing OR unloadable (e.g. built on another host of
            # an NFS home, glibc upgraded since): rebuild in place.
            # Holding the module lock across the one-time compile is
            # the point: a second caller must wait for THIS build, not
            # race a duplicate compiler into the same cache path.
            # dplint: allow(DP505) one-time build serializes callers
            if not _build(cached):
                _build_failed = True  # no compiler: available() -> False
                return None
            lib = _try_load(cached)
        if lib is None:
            _build_failed = True
            return None
        lib.tpudp_cpu_count.restype = ctypes.c_int
        lib.tpudp_hostname.argtypes = [ctypes.c_char_p, ctypes.c_int]
        lib.tpudp_hostname.restype = ctypes.c_int
        lib.tpudp_ring_create.argtypes = [
            ctypes.c_char_p, ctypes.c_int, ctypes.c_int, ctypes.c_int,
            ctypes.c_int,
        ]
        lib.tpudp_ring_create.restype = ctypes.c_void_p
        lib.tpudp_ring_allreduce.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_float), ctypes.c_int64,
            ctypes.c_int,
        ]
        lib.tpudp_ring_allreduce.restype = ctypes.c_int
        lib.tpudp_ring_broadcast.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64, ctypes.c_int,
        ]
        lib.tpudp_ring_broadcast.restype = ctypes.c_int
        lib.tpudp_ring_allgather.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
        ]
        lib.tpudp_ring_allgather.restype = ctypes.c_int
        lib.tpudp_ring_reduce_scatter.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_float), ctypes.c_int64,
            ctypes.POINTER(ctypes.c_float), ctypes.c_int,
        ]
        lib.tpudp_ring_reduce_scatter.restype = ctypes.c_int
        lib.tpudp_ring_reduce.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_float), ctypes.c_int64,
            ctypes.c_int, ctypes.c_int,
        ]
        lib.tpudp_ring_reduce.restype = ctypes.c_int
        lib.tpudp_ring_send_next.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
        ]
        lib.tpudp_ring_send_next.restype = ctypes.c_int
        lib.tpudp_ring_recv_prev.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
        ]
        lib.tpudp_ring_recv_prev.restype = ctypes.c_int
        lib.tpudp_ring_shift.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64, ctypes.c_int,
        ]
        lib.tpudp_ring_shift.restype = ctypes.c_int
        lib.tpudp_ring_barrier.argtypes = [ctypes.c_void_p]
        lib.tpudp_ring_barrier.restype = ctypes.c_int
        lib.tpudp_ring_destroy.argtypes = [ctypes.c_void_p]
        _lib = lib
        return _lib


def available() -> bool:
    """True when the C++ library built/loaded successfully."""
    return _get() is not None


def cpu_count() -> int:
    lib = _get()
    if lib is not None:
        n = lib.tpudp_cpu_count()
        if n > 0:
            return n
    return os.cpu_count() or 1


def hostname() -> str:
    lib = _get()
    if lib is not None:
        buf = ctypes.create_string_buffer(256)
        if lib.tpudp_hostname(buf, 256) == 0:
            return buf.value.decode()
    return _socket.gethostname()


class Ring:
    """A TCP ring over `world` processes for host-side collectives.

    The Gloo-style fallback for the collective layer (SURVEY.md §2B row 1);
    semantically identical to the XLA path: allreduce(sum/mean) + barrier.
    """

    def __init__(self, host: str, base_port: int, rank: int, world: int,
                 timeout_ms: int = 10_000):
        lib = _get()
        if lib is None:
            raise RuntimeError("native host library unavailable (g++ build failed)")
        self._lib = lib
        self.rank = rank
        self.world = world
        self._ctx = lib.tpudp_ring_create(
            host.encode(), base_port, rank, world, timeout_ms
        )
        if not self._ctx and world > 1:
            raise RuntimeError(
                f"ring rendezvous failed (rank {rank}/{world} @ {host}:{base_port})"
            )

    def allreduce(self, array: np.ndarray, op: str = "sum") -> np.ndarray:
        """In-place float32 allreduce across the ring; returns the array."""
        arr = np.ascontiguousarray(array, dtype=np.float32)
        opc = {"sum": 0, "mean": 1}[op]
        rc = self._lib.tpudp_ring_allreduce(
            self._ctx,
            arr.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            arr.size,
            opc,
        )
        if rc != 0:
            raise RuntimeError("ring allreduce failed")
        return arr

    def broadcast(self, array: np.ndarray, root: int = 0) -> np.ndarray:
        """In-place byte broadcast from `root` to all ranks (any dtype).

        Host-side analogue of DDP's rank-0 param replication at wrap time
        (`/root/reference/cifar_example_ddp.py:83`): non-root contents are
        overwritten with root's.
        """
        arr = np.ascontiguousarray(array)
        if self.world == 1:
            return arr
        rc = self._lib.tpudp_ring_broadcast(
            self._ctx, arr.ctypes.data_as(ctypes.c_void_p), arr.nbytes, root
        )
        if rc != 0:
            raise RuntimeError("ring broadcast failed")
        if isinstance(array, np.ndarray) and arr is not array:
            array[...] = arr  # ascontiguousarray copied; honor in-place
        return arr

    def allgather(self, array: np.ndarray) -> np.ndarray:
        """Gather equal-shape per-rank arrays; returns (world, *shape)."""
        arr = np.ascontiguousarray(array)
        out = np.empty((self.world,) + arr.shape, dtype=arr.dtype)
        out[self.rank] = arr
        if self.world == 1:
            return out
        rc = self._lib.tpudp_ring_allgather(
            self._ctx, out.ctypes.data_as(ctypes.c_void_p), arr.nbytes
        )
        if rc != 0:
            raise RuntimeError("ring allgather failed")
        return out

    def reduce_scatter(self, array: np.ndarray, op: str = "sum") -> np.ndarray:
        """Reduce `array` (shape (world, *seg)) across ranks; return this
        rank's reduced segment (shape seg) — ncclReduceScatter semantics."""
        if array.shape[0] != self.world:
            raise ValueError(
                f"reduce_scatter input must have leading dim world={self.world}, "
                f"got {array.shape}"
            )
        # Always copy: the C schedule accumulates into its input buffer, and
        # NCCL's sendbuff is const — the caller's array must stay intact.
        arr = np.array(array, dtype=np.float32, order="C", copy=True)
        seg_shape = arr.shape[1:]
        out = np.empty(seg_shape, dtype=np.float32)
        seg_n = int(np.prod(seg_shape, dtype=np.int64)) if seg_shape else 1
        rc = self._lib.tpudp_ring_reduce_scatter(
            self._ctx,
            arr.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            seg_n,
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            {"sum": 0, "mean": 1}[op],
        )
        if rc != 0:
            raise RuntimeError("ring reduce_scatter failed")
        return out

    def reduce(self, array: np.ndarray, root: int = 0,
               op: str = "sum") -> np.ndarray:
        """Reduce to `root` (ncclReduce semantics): root's returned array
        holds the reduction; other ranks get their input back unchanged.
        The caller's array is never mutated (const sendbuff, as in NCCL)."""
        arr = np.array(array, dtype=np.float32, order="C", copy=True)
        rc = self._lib.tpudp_ring_reduce(
            self._ctx,
            arr.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            arr.size,
            root,
            {"sum": 0, "mean": 1}[op],
        )
        if rc != 0:
            raise RuntimeError("ring reduce failed")
        return arr

    def send_next(self, array: np.ndarray) -> None:
        """Point-to-point: send raw bytes to rank (rank+1) % world. Pair
        with the receiver's `recv_prev` — the neighbor send/recv every ring
        schedule is built from.

        Rendezvous-blocking, like an *ungrouped* ncclSend: if every rank
        calls send_next before recv_prev, payloads beyond the kernel socket
        buffer deadlock. For the symmetric everyone-sends-everyone-receives
        pattern use :meth:`exchange` (the grouped sendrecv)."""
        arr = np.ascontiguousarray(array)
        rc = self._lib.tpudp_ring_send_next(
            self._ctx, arr.ctypes.data_as(ctypes.c_void_p), arr.nbytes
        )
        if rc != 0:
            raise RuntimeError("ring send_next failed")

    def recv_prev(self, shape, dtype) -> np.ndarray:
        """Point-to-point: receive an array of `shape`/`dtype` from rank
        (rank-1) % world."""
        out = np.empty(shape, dtype=dtype)
        rc = self._lib.tpudp_ring_recv_prev(
            self._ctx, out.ctypes.data_as(ctypes.c_void_p), out.nbytes
        )
        if rc != 0:
            raise RuntimeError("ring recv_prev failed")
        return out

    def exchange(self, array: np.ndarray) -> np.ndarray:
        """Grouped neighbor sendrecv: send `array` to rank+1 while receiving
        rank-1's array (send/recv overlapped on a sender thread in C — no
        socket-buffer deadlock at any payload size). The ncclGroupStart/
        ncclSend/ncclRecv/ncclGroupEnd pattern for symmetric neighbor p2p;
        the caller's array is left intact."""
        return self.shift(np.array(array, order="C", copy=True), k=1)

    def shift(self, array: np.ndarray, k: int = 1) -> np.ndarray:
        """Collective shift-by-k (host `lax.ppermute` analogue): returns the
        array that started on rank (rank - k) % world. In place when the
        input is already contiguous (like :meth:`allreduce`); use
        :meth:`exchange` for a non-mutating k=1 shift."""
        arr = np.ascontiguousarray(array)
        rc = self._lib.tpudp_ring_shift(
            self._ctx, arr.ctypes.data_as(ctypes.c_void_p), arr.nbytes, int(k)
        )
        if rc != 0:
            raise RuntimeError("ring shift failed")
        return arr

    def barrier(self) -> None:
        if self._lib.tpudp_ring_barrier(self._ctx) != 0:
            raise RuntimeError("ring barrier failed")

    def close(self) -> None:
        if getattr(self, "_ctx", None):
            self._lib.tpudp_ring_destroy(self._ctx)
            self._ctx = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


def ring_allreduce(ring: Ring, array: np.ndarray, op: str = "sum") -> np.ndarray:
    return ring.allreduce(array, op)


def ring_barrier(ring: Ring) -> None:
    ring.barrier()
