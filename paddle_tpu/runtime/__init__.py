"""Native runtime bindings (ref: paddle/fluid/pybind/ binds the reference's
C++ core; here ctypes over the C ABI in csrc/pd_runtime.h — no pybind11).

Components (see csrc/ for the C++ side):

- ``HostAllocator`` — best-fit caching host allocator (the pinned staging
  arena; ref: paddle/fluid/memory/allocation/auto_growth_best_fit_allocator.cc)
- ``BlockingQueue`` — bounded MPMC prefetch queue (ref: reader blocking queue)
- ``TCPStoreServer`` / ``TCPStore`` — rendezvous KV store
  (ref: paddle/phi/core/distributed/store/tcp_store.cc)
- tracer functions — host span tracer w/ chrome-trace export
  (ref: paddle/fluid/platform/profiler/)

If the shared library is missing or older than its sources under csrc/, it
is built on demand with ``make`` (cached thereafter).  If no toolchain is
available, pure-Python fallbacks speaking the same TCP wire protocol keep
everything functional (slower): mixed clusters of native and fallback
processes interoperate.
"""
from __future__ import annotations

import ctypes
import itertools
import os
import queue as _pyqueue
import socket
import socketserver
import struct
import subprocess
import threading
import time
from typing import Optional

_CSRC = os.path.join(os.path.dirname(__file__), os.pardir, "csrc")
# PD_RUNTIME_LIB overrides the lib path (sanitizer builds, system installs)
_LIB_PATH = os.environ.get("PD_RUNTIME_LIB") or os.path.abspath(
    os.path.join(_CSRC, "libpd_runtime.so"))

_lib = None
_load_attempted = False
_load_error = None


def _stale() -> bool:
    """True where the library is missing or older than a file under csrc/
    it is built from: a library left over from other sources is rebuilt
    or reported, never loaded in silence."""
    if not os.path.exists(_LIB_PATH):
        return True
    built = os.path.getmtime(_LIB_PATH)
    return any(os.path.getmtime(os.path.join(_CSRC, f)) > built
               for f in os.listdir(_CSRC)
               if f.endswith((".cc", ".h")) or f == "Makefile")


def _try_build() -> bool:
    """``make`` into a name of this process's own, then rename over the
    library: several processes may build at once (test workers on a fresh
    checkout) and none may load a half-written file."""
    csrc = os.path.abspath(_CSRC)
    tmp = os.path.join(csrc, f"libpd_runtime.build{os.getpid()}.so")
    try:
        r = subprocess.run(
            ["make", "-C", csrc, f"OUT={os.path.basename(tmp)}"],
            capture_output=True, timeout=300)
        if r.returncode != 0:
            return False
        os.replace(tmp, _LIB_PATH)
        return True
    except (OSError, subprocess.SubprocessError):
        return False
    finally:
        if os.path.exists(tmp):
            os.remove(tmp)


def _bind(lib):
    c = ctypes
    u64p = c.POINTER(c.c_uint64)
    sigs = {
        "pd_runtime_abi_version": (c.c_int, []),
        "pd_last_error": (c.c_char_p, []),
        "pd_flag_define": (c.c_int, [c.c_char_p, c.c_char_p, c.c_char_p]),
        "pd_flag_set": (c.c_int, [c.c_char_p, c.c_char_p]),
        "pd_flag_get": (c.c_char_p, [c.c_char_p]),
        "pd_flags_list": (c.c_int, [c.c_char_p, c.c_int]),
        "pd_allocator_create": (c.c_void_p, [c.c_uint64]),
        "pd_allocator_destroy": (None, [c.c_void_p]),
        "pd_alloc": (c.c_void_p, [c.c_void_p, c.c_uint64]),
        "pd_free": (None, [c.c_void_p, c.c_void_p]),
        "pd_allocator_stats": (None, [c.c_void_p, u64p, u64p, u64p]),
        "pd_allocator_release_free": (c.c_uint64, [c.c_void_p]),
        "pd_queue_create": (c.c_void_p, [c.c_int]),
        "pd_queue_destroy": (None, [c.c_void_p]),
        "pd_queue_push": (c.c_int, [c.c_void_p, c.c_uint64, c.c_double]),
        "pd_queue_pop": (c.c_int, [c.c_void_p, u64p, c.c_double]),
        "pd_queue_close": (None, [c.c_void_p]),
        "pd_queue_size": (c.c_int, [c.c_void_p]),
        "pd_queue_is_closed": (c.c_int, [c.c_void_p]),
        "pd_store_server_start": (c.c_void_p, [c.c_int]),
        "pd_store_server_port": (c.c_int, [c.c_void_p]),
        "pd_store_server_stop": (None, [c.c_void_p]),
        "pd_store_client_connect": (c.c_void_p,
                                    [c.c_char_p, c.c_int, c.c_double]),
        "pd_store_client_close": (None, [c.c_void_p]),
        "pd_store_set": (c.c_int,
                         [c.c_void_p, c.c_char_p, c.c_char_p, c.c_int]),
        "pd_store_get": (c.c_int, [c.c_void_p, c.c_char_p, c.c_char_p,
                                   c.c_int, c.c_double]),
        "pd_store_add": (c.c_int64, [c.c_void_p, c.c_char_p, c.c_int64]),
        "pd_store_wait": (c.c_int, [c.c_void_p, c.c_char_p, c.c_double]),
        "pd_store_delete": (c.c_int, [c.c_void_p, c.c_char_p]),
        "pd_store_num_keys": (c.c_int, [c.c_void_p]),
        "pd_tracer_start": (None, []),
        "pd_tracer_stop": (None, []),
        "pd_tracer_is_recording": (c.c_int, []),
        "pd_tracer_clear": (None, []),
        "pd_trace_begin": (None, [c.c_char_p]),
        "pd_trace_end": (None, []),
        "pd_trace_instant": (None, [c.c_char_p]),
        "pd_trace_counter": (None, [c.c_char_p, c.c_double]),
        "pd_tracer_export": (c.c_int, [c.c_char_p, c.c_int]),
    }
    for name, (res, args) in sigs.items():
        fn = getattr(lib, name)
        fn.restype = res
        fn.argtypes = args
    return lib


def load():
    """Load (building if needed) the native library; None if unavailable."""
    global _lib, _load_attempted, _load_error
    if _lib is not None or _load_attempted:
        return _lib
    _load_attempted = True
    if os.environ.get("PD_DISABLE_NATIVE", "0") == "1":
        _load_error = "disabled via PD_DISABLE_NATIVE"
        return None
    if (not os.environ.get("PD_RUNTIME_LIB") and _stale()
            and not _try_build()):
        _load_error = ("libpd_runtime.so is missing or older than csrc/ "
                       "and the build failed")
        return None
    try:
        _lib = _bind(ctypes.CDLL(_LIB_PATH))
        if _lib.pd_runtime_abi_version() != 1:
            _load_error = "ABI version mismatch"
            _lib = None
    except OSError as e:  # pragma: no cover
        _load_error = str(e)
        _lib = None
    if _lib is not None:
        _flush_pending_mirrors(_lib)
    return _lib


def available() -> bool:
    return load() is not None


def load_error() -> Optional[str]:
    load()
    return _load_error


# --------------------------------------------------------------------------
# Host allocator
# --------------------------------------------------------------------------
class HostAllocator:
    """Caching host allocator handing out ctypes-backed buffers.

    ``alloc(n)`` returns a writable memoryview; ``free(mv)`` recycles it.
    Falls back to plain bytearrays (no caching) without the native lib.
    """

    def __init__(self, chunk_bytes: int = 64 << 20):
        self._lib = load()
        self._by_address = {}
        if self._lib:
            self._h = self._lib.pd_allocator_create(chunk_bytes)
        else:
            self._h = None

    def alloc(self, nbytes: int) -> memoryview:
        if self._h:
            ptr = self._lib.pd_alloc(self._h, nbytes)
            if not ptr:
                raise MemoryError(self._lib.pd_last_error().decode())
            buf = (ctypes.c_char * nbytes).from_address(ptr)
            mv = memoryview(buf).cast("B")
            self._by_address[id(buf)] = (ptr, buf)
            return mv
        return memoryview(bytearray(nbytes))

    def free(self, mv: memoryview):
        if not self._h:
            return
        try:
            obj = mv.obj
        except ValueError:  # already released (double free) -> no-op
            return
        ent = self._by_address.pop(id(obj), None)
        if ent is not None:
            mv.release()
            self._lib.pd_free(self._h, ent[0])

    def stats(self) -> dict:
        if not self._h:
            return {"allocated": 0, "reserved": 0, "peak": 0}
        a = ctypes.c_uint64()
        r = ctypes.c_uint64()
        p = ctypes.c_uint64()
        self._lib.pd_allocator_stats(self._h, ctypes.byref(a),
                                     ctypes.byref(r), ctypes.byref(p))
        return {"allocated": a.value, "reserved": r.value, "peak": p.value}

    def release_free(self) -> int:
        if not self._h:
            return 0
        return self._lib.pd_allocator_release_free(self._h)

    def __del__(self):
        if getattr(self, "_h", None) and self._lib:
            self._lib.pd_allocator_destroy(self._h)
            self._h = None


# --------------------------------------------------------------------------
# Blocking queue (native handles mapped to Python objects via a registry)
# --------------------------------------------------------------------------
class BlockingQueue:
    """Bounded blocking queue for DataLoader prefetch.

    Native path: the C++ queue carries uint64 tokens, blocking/backpressure
    happens off-GIL; a Python-side registry maps tokens to batch objects.
    """

    def __init__(self, capacity: int):
        self._lib = load()
        if self._lib:
            self._q = self._lib.pd_queue_create(capacity)
            self._registry = {}
            self._reg_lock = threading.Lock()
            self._ids = itertools.count(1)
        else:
            self._q = None
            self._fallback = _PyBlockingQueue(capacity)

    def push(self, obj, timeout: float = -1.0) -> bool:
        """Returns False on timeout; raises RuntimeError if closed."""
        if self._q:
            with self._reg_lock:
                h = next(self._ids)
                self._registry[h] = obj
            rc = self._lib.pd_queue_push(self._q, h, timeout)
            if rc != 0:
                with self._reg_lock:
                    self._registry.pop(h, None)
            if rc == -2:
                raise RuntimeError("queue closed")
            return rc == 0
        return self._fallback.push(obj, timeout)

    def pop(self, timeout: float = -1.0):
        """Returns the object, or raises queue.Empty on timeout /
        RuntimeError("queue closed") when closed and drained."""
        if self._q:
            h = ctypes.c_uint64()
            rc = self._lib.pd_queue_pop(self._q, ctypes.byref(h), timeout)
            if rc == -1:
                raise _pyqueue.Empty()
            if rc == -2:
                raise RuntimeError("queue closed")
            with self._reg_lock:
                return self._registry.pop(h.value)
        return self._fallback.pop(timeout)

    def close(self):
        if self._q:
            self._lib.pd_queue_close(self._q)
        else:
            self._fallback.close()

    def qsize(self) -> int:
        if self._q:
            return self._lib.pd_queue_size(self._q)
        return self._fallback.qsize()

    def __del__(self):
        if getattr(self, "_q", None) and self._lib:
            self._lib.pd_queue_close(self._q)
            self._lib.pd_queue_destroy(self._q)
            self._q = None


class _PyBlockingQueue:
    """Fallback with the native queue's exact semantics: close() unblocks
    every waiter; pop on a closed+drained queue raises RuntimeError."""

    def __init__(self, capacity: int):
        self._cap = max(1, capacity)
        self._items = []
        self._cond = threading.Condition()
        self._closed = False

    def push(self, obj, timeout: float = -1.0) -> bool:
        with self._cond:
            ok = self._cond.wait_for(
                lambda: self._closed or len(self._items) < self._cap,
                None if timeout < 0 else timeout)
            if not ok:
                return False
            if self._closed:
                raise RuntimeError("queue closed")
            self._items.append(obj)
            self._cond.notify_all()
            return True

    def pop(self, timeout: float = -1.0):
        with self._cond:
            ok = self._cond.wait_for(
                lambda: self._closed or self._items,
                None if timeout < 0 else timeout)
            if not ok:
                raise _pyqueue.Empty()
            if not self._items:
                raise RuntimeError("queue closed")
            obj = self._items.pop(0)
            self._cond.notify_all()
            return obj

    def close(self):
        with self._cond:
            self._closed = True
            self._cond.notify_all()

    def qsize(self) -> int:
        with self._cond:
            return len(self._items)


# --------------------------------------------------------------------------
# TCP store — wire protocol shared between native and fallback (see
# csrc/tcp_store.cc header comment for framing)
# --------------------------------------------------------------------------
_CMD_SET, _CMD_GET, _CMD_ADD, _CMD_WAIT, _CMD_DEL, _CMD_NUMKEYS, _CMD_GETWAIT \
    = 1, 2, 3, 4, 5, 6, 7


class _PyStoreHandler(socketserver.BaseRequestHandler):
    def _recv_all(self, n):
        data = b""
        while len(data) < n:
            chunk = self.request.recv(n - len(data))
            if not chunk:
                raise ConnectionError("peer closed")
            data += chunk
        return data

    def handle(self):
        srv = self.server.pd_server
        while True:
            try:
                cmd = self._recv_all(1)[0]
                (klen,) = struct.unpack("<I", self._recv_all(4))
                key = self._recv_all(klen).decode()
                (vlen,) = struct.unpack("<I", self._recv_all(4))
                val = self._recv_all(vlen)
            except (ConnectionError, OSError):
                return
            status, payload = 0, b""
            with srv.cond:
                if cmd == _CMD_SET:
                    srv.data[key] = val
                    srv.cond.notify_all()
                elif cmd == _CMD_GET:
                    if key in srv.data:
                        payload = srv.data[key]
                    else:
                        status = -2
                elif cmd in (_CMD_WAIT, _CMD_GETWAIT):
                    (timeout_s,) = struct.unpack("<d", val)
                    deadline = (None if timeout_s < 0
                                else time.monotonic() + timeout_s)
                    while key not in srv.data and not srv.stopping:
                        remaining = (None if deadline is None
                                     else deadline - time.monotonic())
                        if remaining is not None and remaining <= 0:
                            break
                        srv.cond.wait(remaining)
                    if key not in srv.data:
                        status = -1
                    elif cmd == _CMD_GETWAIT:
                        payload = srv.data[key]
                elif cmd == _CMD_ADD:
                    (delta,) = struct.unpack("<q", val)
                    cur = struct.unpack(
                        "<q", srv.data.get(key, b"\0" * 8))[0] + delta
                    srv.data[key] = struct.pack("<q", cur)
                    srv.cond.notify_all()
                    payload = srv.data[key]
                elif cmd == _CMD_DEL:
                    status = 0 if srv.data.pop(key, None) is not None else -2
                elif cmd == _CMD_NUMKEYS:
                    status = len(srv.data)
                else:
                    status = -3
            try:
                self.request.sendall(
                    struct.pack("<qI", status, len(payload)) + payload)
            except OSError:
                return


class _PyThreadedServer(socketserver.ThreadingTCPServer):
    allow_reuse_address = True
    daemon_threads = True


class TCPStoreServer:
    """Rendezvous store server; native when possible, Python otherwise."""

    def __init__(self, port: int = 0):
        self._lib = load()
        if self._lib:
            self._h = self._lib.pd_store_server_start(port)
            if not self._h:
                raise RuntimeError("TCPStoreServer: " +
                                   self._lib.pd_last_error().decode())
            self._port = self._lib.pd_store_server_port(self._h)
        else:
            self._h = None
            self._srv = _PyThreadedServer(("0.0.0.0", port), _PyStoreHandler)
            self._srv.pd_server = self
            self.data = {}
            self.cond = threading.Condition()
            self.stopping = False
            self._port = self._srv.server_address[1]
            self._thread = threading.Thread(
                target=self._srv.serve_forever, daemon=True)
            self._thread.start()

    @property
    def port(self) -> int:
        return self._port

    def stop(self):
        if self._h:
            self._lib.pd_store_server_stop(self._h)
            self._h = None
        elif getattr(self, "_srv", None):
            with self.cond:
                self.stopping = True
                self.cond.notify_all()
            self._srv.shutdown()
            self._srv.server_close()
            self._srv = None

    def __del__(self):
        try:
            self.stop()
        except Exception:
            pass


class TCPStore:
    """Client for TCPStoreServer (either implementation)."""

    def __init__(self, host: str, port: int, timeout: float = 60.0):
        self._lib = load()
        self._host, self._port = host, port
        if self._lib:
            self._c = self._lib.pd_store_client_connect(
                host.encode(), port, timeout)
            if not self._c:
                raise ConnectionError("TCPStore: " +
                                      self._lib.pd_last_error().decode())
        else:
            self._c = None
            self._lock = threading.Lock()
            deadline = time.monotonic() + timeout
            while True:
                try:
                    self._sock = socket.create_connection((host, port),
                                                          timeout=5.0)
                    self._sock.settimeout(None)
                    self._sock.setsockopt(socket.IPPROTO_TCP,
                                          socket.TCP_NODELAY, 1)
                    break
                except OSError:
                    if time.monotonic() > deadline:
                        raise ConnectionError(
                            f"TCPStore: connect {host}:{port} timed out"
                        ) from None
                    time.sleep(0.05)

    # -- python-fallback request path --
    def _request(self, cmd, key: str, val: bytes):
        kb = key.encode()
        msg = (struct.pack("<BI", cmd, len(kb)) + kb +
               struct.pack("<I", len(val)) + val)
        with self._lock:
            self._sock.sendall(msg)
            hdr = b""
            while len(hdr) < 12:
                chunk = self._sock.recv(12 - len(hdr))
                if not chunk:
                    raise ConnectionError("store server closed")
                hdr += chunk
            status, plen = struct.unpack("<qI", hdr)
            payload = b""
            while len(payload) < plen:
                chunk = self._sock.recv(plen - len(payload))
                if not chunk:
                    raise ConnectionError("store server closed")
                payload += chunk
        return status, payload

    def set(self, key: str, value: bytes):
        if isinstance(value, str):
            value = value.encode()
        if self._c:
            rc = self._lib.pd_store_set(self._c, key.encode(), value,
                                        len(value))
            if rc < 0:
                raise ConnectionError("store set failed")
        else:
            self._request(_CMD_SET, key, value)

    def get(self, key: str, timeout: float = -1.0) -> bytes:
        """Blocks until the key exists (or timeout -> TimeoutError)."""
        if self._c:
            cap = 1 << 16
            while True:
                buf = ctypes.create_string_buffer(cap)
                n = self._lib.pd_store_get(self._c, key.encode(), buf, cap,
                                           timeout)
                if n == -1:
                    raise TimeoutError(f"store get({key!r}) timed out")
                if n < 0:
                    raise ConnectionError("store get failed")
                if n <= cap:
                    return buf.raw[:n]
                cap = n  # payload larger than buffer: re-request
        status, payload = self._request(_CMD_GETWAIT, key,
                                        struct.pack("<d", timeout))
        if status == -1:
            raise TimeoutError(f"store get({key!r}) timed out")
        if status < 0:
            raise ConnectionError("store get failed")
        return payload

    def add(self, key: str, delta: int) -> int:
        if self._c:
            v = self._lib.pd_store_add(self._c, key.encode(), delta)
            if v == -(2 ** 63):
                raise ConnectionError("store add failed")
            return v
        status, payload = self._request(_CMD_ADD, key,
                                        struct.pack("<q", delta))
        if status < 0:
            raise ConnectionError("store add failed")
        return struct.unpack("<q", payload)[0]

    def wait(self, key: str, timeout: float = -1.0):
        if self._c:
            rc = self._lib.pd_store_wait(self._c, key.encode(), timeout)
            if rc == -1:
                raise TimeoutError(f"store wait({key!r}) timed out")
            if rc < 0:
                raise ConnectionError("store wait failed")
            return
        status, _ = self._request(_CMD_WAIT, key, struct.pack("<d", timeout))
        if status == -1:
            raise TimeoutError(f"store wait({key!r}) timed out")
        if status < 0:
            raise ConnectionError("store wait failed")

    def delete(self, key: str) -> bool:
        if self._c:
            return self._lib.pd_store_delete(self._c, key.encode()) == 0
        status, _ = self._request(_CMD_DEL, key, b"")
        return status == 0

    def num_keys(self) -> int:
        if self._c:
            return self._lib.pd_store_num_keys(self._c)
        status, _ = self._request(_CMD_NUMKEYS, "", b"")
        return int(status)

    def close(self):
        if self._c:
            self._lib.pd_store_client_close(self._c)
            self._c = None
        elif getattr(self, "_sock", None):
            self._sock.close()
            self._sock = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


# --------------------------------------------------------------------------
# Tracer
# --------------------------------------------------------------------------
_py_events = []
_py_recording = False
_py_tls = threading.local()


def tracer_start():
    global _py_recording
    lib = load()
    if lib:
        lib.pd_tracer_start()
    else:
        _py_recording = True


def tracer_stop():
    global _py_recording
    lib = load()
    if lib:
        lib.pd_tracer_stop()
    else:
        _py_recording = False


def tracer_clear():
    lib = load()
    if lib:
        lib.pd_tracer_clear()
    else:
        _py_events.clear()


def trace_begin(name: str):
    lib = load()
    if lib:
        lib.pd_trace_begin(name.encode())
    elif _py_recording:
        stack = getattr(_py_tls, "stack", None)
        if stack is None:
            stack = _py_tls.stack = []
        stack.append((name, time.monotonic_ns()))


def trace_end():
    lib = load()
    if lib:
        lib.pd_trace_end()
    elif _py_recording:
        stack = getattr(_py_tls, "stack", [])
        if stack:
            name, begin = stack.pop()
            _py_events.append({
                "ph": "X", "name": name, "pid": 0,
                "tid": threading.get_ident() % 100000,
                "ts": begin / 1000.0,
                "dur": (time.monotonic_ns() - begin) / 1000.0})


def trace_instant(name: str):
    lib = load()
    if lib:
        lib.pd_trace_instant(name.encode())
    elif _py_recording:
        _py_events.append({"ph": "i", "name": name, "pid": 0,
                           "tid": threading.get_ident() % 100000,
                           "ts": time.monotonic_ns() / 1000.0, "s": "t"})


def trace_counter(name: str, value: float):
    lib = load()
    if lib:
        lib.pd_trace_counter(name.encode(), value)
    elif _py_recording:
        _py_events.append({"ph": "C", "name": name, "pid": 0,
                           "tid": threading.get_ident() % 100000,
                           "ts": time.monotonic_ns() / 1000.0,
                           "args": {"value": value}})


def tracer_export() -> str:
    """Chrome-trace JSON for everything recorded so far."""
    lib = load()
    if lib:
        n = lib.pd_tracer_export(None, 0)
        buf = ctypes.create_string_buffer(n + 1)
        lib.pd_tracer_export(buf, n + 1)
        return buf.value.decode()
    import json
    return json.dumps({"traceEvents": _py_events})


class RecordSpan:
    """Context manager emitting one host-tracer span."""

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        trace_begin(self.name)
        return self

    def __exit__(self, *exc):
        trace_end()
        return False


# --------------------------------------------------------------------------
# Native flags mirror: keep the C++ side able to read framework flags.
# Mirroring must NOT trigger a build — flags are defined at import time and a
# cold import must not block on `make`. Defines/sets queue up and flush once
# the library is loaded for another reason.
# --------------------------------------------------------------------------
_pending_mirrors = []


def _flush_pending_mirrors(lib):
    for op, args in _pending_mirrors:
        if op == "define":
            lib.pd_flag_define(*args)
        else:
            lib.pd_flag_set(*args)
    _pending_mirrors.clear()


def mirror_flag_define(name: str, default, help_str: str = ""):
    args = (name.encode(), str(default).encode(), help_str.encode())
    if _lib is not None:
        _lib.pd_flag_define(*args)
    else:
        _pending_mirrors.append(("define", args))


def mirror_flag_set(name: str, value):
    args = (name.encode(), str(value).encode())
    if _lib is not None:
        _lib.pd_flag_set(*args)
    else:
        _pending_mirrors.append(("set", args))


def native_flag_get(name: str) -> Optional[str]:
    lib = load()
    if lib:
        v = lib.pd_flag_get(name.encode())
        return v.decode() if v is not None else None
    return None


class DeadlockWatchdog:
    """Hang detector for collective regions (SURVEY.md §5.2: the TPU build's
    answer to NCCL hang debugging — the reference relies on env timeouts).

    Wrap a collective-heavy region; if it doesn't finish within ``timeout``
    seconds the watchdog dumps every thread's stack to stderr (and optionally
    invokes ``on_timeout``), so a stuck psum/all_gather across ranks leaves a
    diagnosable trace instead of a silent hang.

        with rt.DeadlockWatchdog(timeout=300, tag="allreduce"):
            out = step(params, batch)

    Re-entrant and cheap: one timer thread per active region.
    """

    def __init__(self, timeout: float, tag: str = "collective",
                 on_timeout=None, abort: bool = False):
        self.timeout = timeout
        self.tag = tag
        self.on_timeout = on_timeout
        self.abort = abort
        self._timers = []   # stack: nested regions each get their own timer
        self.fired = False

    def _fire(self):
        import sys
        self.fired = True
        try:
            sys.stderr.write(
                f"\n=== DeadlockWatchdog[{self.tag}]: no completion within "
                f"{self.timeout}s — dumping all thread stacks ===\n")
            import faulthandler
            # needs a real fd; captured/replaced stderr (pytest) lacks one
            faulthandler.dump_traceback(file=sys.stderr, all_threads=True)
        except Exception:
            import traceback
            for tid, frame in sys._current_frames().items():
                sys.stderr.write(f"--- thread {tid} ---\n")
                sys.stderr.write("".join(traceback.format_stack(frame)))
        finally:
            if self.on_timeout is not None:
                self.on_timeout()
            if self.abort:
                import os
                os._exit(99)

    def __enter__(self):
        timer = threading.Timer(self.timeout, self._fire)
        timer.daemon = True
        timer.start()
        self._timers.append(timer)
        return self

    def __exit__(self, *exc):
        if self._timers:
            self._timers.pop().cancel()
        return False
