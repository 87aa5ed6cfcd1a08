"""Local agent↔worker IPC: named shared memory, queue, dict, lock.

Reference: dlrover/python/common/multi_process.py:225,346,453,537
(SharedLock/SharedQueue/SharedDict over unix sockets + POSIX SharedMemory
with no resource-tracker unlink). Same design: the *agent* process is the
server side, workers connect by name under a per-job socket directory, and
checkpoint tensor payloads ride named POSIX shared memory so a worker crash
never loses the staged bytes.
"""

import json
import os
import shutil
import socket
import socketserver
import threading
from multiprocessing import shared_memory, resource_tracker
from typing import Any, Dict, List, Optional

from dlrover_tpu.common.log import get_logger

logger = get_logger(__name__)

_SOCKET_DIR = os.environ.get(
    "DLROVER_TPU_SOCK_DIR", "/tmp/dlrover_tpu_sockets"
)


def _socket_path(name: str) -> str:
    os.makedirs(_SOCKET_DIR, exist_ok=True)
    run_id = os.environ.get("DLROVER_TPU_RUN_ID", "default")
    return os.path.join(_SOCKET_DIR, f"{run_id}_{name}.sock")


def broker_alive(name: str) -> bool:
    """True iff a live broker is serving ``name``'s socket.

    The socket FILE alone proves nothing: a SIGKILLed agent leaves its
    socket behind, and a later process keying "is an agent hosting the
    brokers?" off ``os.path.exists`` would run as a client against a
    broker that will never answer. Probe with a real connect and unlink
    the corpse on refusal so the namespace heals for the next caller.
    """
    path = _socket_path(name)
    if not os.path.exists(path):
        return False
    with socket.socket(socket.AF_UNIX, socket.SOCK_STREAM) as s:
        s.settimeout(1.0)
        try:
            s.connect(path)
            return True
        except OSError:
            logger.warning(
                "stale IPC socket %s (broker gone); removing it", path
            )
            try:
                os.unlink(path)
            except OSError:
                pass
            return False


def attach_shared_memory(name: str) -> shared_memory.SharedMemory:
    """Attach without registering in the resource tracker.

    Python's tracker unlinks attached segments when *any* process exits —
    exactly wrong for checkpoint staging that must outlive worker crashes
    (the reference patches this the same way, multi_process.py:537).
    """
    shm = shared_memory.SharedMemory(name=name)
    try:
        resource_tracker.unregister(shm._name, "shared_memory")  # noqa: SLF001
    except Exception:  # noqa: BLE001
        pass
    return shm


def create_shared_memory(name: str, size: int) -> shared_memory.SharedMemory:
    try:
        old = attach_shared_memory(name)
        if old.size >= size:
            return old
        old.close()
        old.unlink()
    except FileNotFoundError:
        pass
    # POSIX shm is tmpfs: creating a segment larger than the mount has
    # room for succeeds, and the write that crosses the limit is a
    # SIGBUS. Say it in a sentence first.
    if os.path.isdir("/dev/shm"):
        free = shutil.disk_usage("/dev/shm").free
        if size > free:
            raise RuntimeError(
                f"shared-memory segment {name!r} needs {size / 1e9:.2f} GB "
                f"but /dev/shm has {free / 1e9:.2f} GB free; enlarge it "
                "(or free stale dlrover_tpu_* segments) to stage this state"
            )
    shm = shared_memory.SharedMemory(name=name, create=True, size=size)
    try:
        resource_tracker.unregister(shm._name, "shared_memory")  # noqa: SLF001
    except Exception:  # noqa: BLE001
        pass
    return shm


# ---------------------------------------------------------------------------
# Unix-socket RPC primitives (agent = server, worker = client)
# ---------------------------------------------------------------------------


class _Handler(socketserver.StreamRequestHandler):
    def handle(self):
        try:
            line = self.rfile.readline()
            if not line:
                return
            req = json.loads(line)
            resp = self.server.owner._handle(req)  # type: ignore[attr-defined]
            self.wfile.write((json.dumps(resp) + "\n").encode())
        except Exception as e:  # noqa: BLE001
            try:
                self.wfile.write(
                    (json.dumps({"ok": False, "err": str(e)}) + "\n").encode()
                )
            except Exception:  # noqa: BLE001
                pass


class _LocalServer:
    """One unix-socket server per named primitive."""

    def __init__(self, name: str):
        self.name = name
        self.path = _socket_path(name)
        if os.path.exists(self.path):
            os.unlink(self.path)
        self._server = socketserver.ThreadingUnixStreamServer(
            self.path, _Handler
        )
        self._server.owner = self  # type: ignore[attr-defined]
        self._thread = threading.Thread(
            target=self._server.serve_forever,
            name=f"ipc-{name}",
            daemon=True,
        )
        self._thread.start()

    def _handle(self, req: Dict) -> Dict:
        raise NotImplementedError

    def close(self):
        self._server.shutdown()
        self._server.server_close()
        if os.path.exists(self.path):
            os.unlink(self.path)


def _client_call(name: str, req: Dict, timeout: float = 30.0) -> Dict:
    path = _socket_path(name)
    with socket.socket(socket.AF_UNIX, socket.SOCK_STREAM) as s:
        s.settimeout(timeout)
        s.connect(path)
        s.sendall((json.dumps(req) + "\n").encode())
        buf = b""
        while not buf.endswith(b"\n"):
            chunk = s.recv(65536)
            if not chunk:
                break
            buf += chunk
    return json.loads(buf)


# ---- SharedQueue ----------------------------------------------------------


class SharedQueue(_LocalServer):
    """Agent-side FIFO; workers put/get by name."""

    def __init__(self, name: str):
        super().__init__(f"queue_{name}")
        self._items: List[Any] = []
        self._cond = threading.Condition()

    def _handle(self, req: Dict) -> Dict:
        op = req["op"]
        if op == "put":
            with self._cond:
                self._items.append(req["item"])
                self._cond.notify()
            return {"ok": True}
        if op == "get":
            timeout = req.get("timeout", 0)
            with self._cond:
                if not self._items and timeout:
                    self._cond.wait(timeout)
                if self._items:
                    return {"ok": True, "item": self._items.pop(0)}
            return {"ok": False}
        if op == "qsize":
            with self._cond:
                return {"ok": True, "item": len(self._items)}
        return {"ok": False, "err": f"bad op {op}"}

    # server-side convenience (agent process)
    def get(self, timeout: float = 0) -> Optional[Any]:
        with self._cond:
            if not self._items and timeout:
                self._cond.wait(timeout)
            return self._items.pop(0) if self._items else None

    def put(self, item: Any):
        with self._cond:
            self._items.append(item)
            self._cond.notify()


class SharedQueueClient:
    def __init__(self, name: str):
        self._name = f"queue_{name}"

    def put(self, item: Any) -> bool:
        return _client_call(self._name, {"op": "put", "item": item})["ok"]

    def get(self, timeout: float = 0) -> Optional[Any]:
        resp = _client_call(
            self._name,
            {"op": "get", "timeout": timeout},
            timeout=timeout + 30.0,
        )
        return resp.get("item") if resp.get("ok") else None


# ---- SharedDict -----------------------------------------------------------


class SharedDict(_LocalServer):
    """Agent-side dict; workers set/get JSON values by key."""

    def __init__(self, name: str):
        super().__init__(f"dict_{name}")
        self._data: Dict[str, Any] = {}
        self._lock = threading.Lock()

    def _handle(self, req: Dict) -> Dict:
        op = req["op"]
        if op == "set":
            with self._lock:
                self._data[req["key"]] = req["value"]
            return {"ok": True}
        if op == "get":
            with self._lock:
                if req.get("key") is None:
                    return {"ok": True, "value": dict(self._data)}
                return {"ok": True, "value": self._data.get(req["key"])}
        if op == "delete":
            with self._lock:
                self._data.pop(req["key"], None)
            return {"ok": True}
        return {"ok": False, "err": f"bad op {op}"}

    def set(self, key: str, value: Any):
        with self._lock:
            self._data[key] = value

    def get(self, key: Optional[str] = None) -> Any:
        with self._lock:
            if key is None:
                return dict(self._data)
            return self._data.get(key)


class SharedDictClient:
    def __init__(self, name: str):
        self._name = f"dict_{name}"

    def set(self, key: str, value: Any) -> bool:
        return _client_call(
            self._name, {"op": "set", "key": key, "value": value}
        )["ok"]

    def get(self, key: Optional[str] = None) -> Any:
        return _client_call(self._name, {"op": "get", "key": key}).get("value")

    def delete(self, key: str) -> bool:
        return _client_call(self._name, {"op": "delete", "key": key})["ok"]


# ---- SharedLock -----------------------------------------------------------


class SharedLock(_LocalServer):
    """Agent-hosted mutex shared with workers (non-reentrant)."""

    def __init__(self, name: str):
        super().__init__(f"lock_{name}")
        self._lock = threading.Lock()
        self._holder: Optional[str] = None
        self._cond = threading.Condition()

    def _handle(self, req: Dict) -> Dict:
        op = req["op"]
        owner = req.get("owner", "anon")
        if op == "acquire":
            blocking = req.get("blocking", True)
            timeout = req.get("timeout", 60.0)
            with self._cond:
                if self._holder is None:
                    self._holder = owner
                    return {"ok": True}
                if not blocking:
                    return {"ok": False}
                if self._cond.wait_for(
                    lambda: self._holder is None, timeout
                ):
                    self._holder = owner
                    return {"ok": True}
                return {"ok": False}
        if op == "release":
            with self._cond:
                if self._holder == owner:
                    self._holder = None
                    self._cond.notify()
                    return {"ok": True}
            return {"ok": False}
        if op == "locked":
            with self._cond:
                return {"ok": True, "item": self._holder is not None}
        return {"ok": False, "err": f"bad op {op}"}

    def acquire(self, owner: str = "agent", blocking: bool = True) -> bool:
        return self._handle(
            {"op": "acquire", "owner": owner, "blocking": blocking}
        )["ok"]

    def release(self, owner: str = "agent") -> bool:
        return self._handle({"op": "release", "owner": owner})["ok"]


class SharedLockClient:
    def __init__(self, name: str, owner: Optional[str] = None):
        self._name = f"lock_{name}"
        self._owner = owner or f"pid-{os.getpid()}"

    def acquire(self, blocking: bool = True, timeout: float = 60.0) -> bool:
        return _client_call(
            self._name,
            {
                "op": "acquire",
                "owner": self._owner,
                "blocking": blocking,
                "timeout": timeout,
            },
            timeout=timeout + 30.0,
        )["ok"]

    def release(self) -> bool:
        return _client_call(self._name, {"op": "release", "owner": self._owner})[
            "ok"
        ]
