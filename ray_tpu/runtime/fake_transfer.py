"""Host-memory-backed stand-in for ``jax.experimental.transfer``.

The real ICI/DCN device-to-device path (``device_plane.py``) can only
execute between two processes that each own a real multi-host TPU backend —
unbuildable on CPU (the backend fatally aborts on first pull) and untestable
on a single-process host.  This fake implements the exact surface the
device plane consumes —

    server.address() -> str
    server.await_pull(uuid, array) -> ticket (add_done_callback)
    server.connect(addr) -> connection
    connection.pull(uuid, template) -> jax.Array

— over a plain TCP socket with the staged array's HOST bytes as payload, so
the negotiation protocol (offer → ticket → pull → release → fallback) runs
end-to-end across real process boundaries in any environment.  Enabled via
``RAY_TPU_FAKE_DEVICE_TRANSFER=1`` (``device_plane.transfer_server`` builds
one instead of probing the platform) or injected directly with
``device_plane.install_transfer_server``.

Role parity: the mocked NCCL groups the reference uses to test its channel
negotiation without GPUs (``python/ray/experimental/channel/nccl_group.py:18``
consumers are tested with ``conftest`` mock transports).
"""

from __future__ import annotations

import pickle
import socket
import struct
import threading
from typing import Any, Dict, Optional, Tuple

_LEN = struct.Struct("<Q")


def _send_frame(sock: socket.socket, data: bytes) -> None:
    sock.sendall(_LEN.pack(len(data)) + data)


def _recv_exact(sock: socket.socket, n: int) -> bytes:
    chunks, got = [], 0
    while got < n:
        chunk = sock.recv(min(n - got, 1 << 20))
        if not chunk:
            raise ConnectionError("fake transfer socket closed")
        chunks.append(chunk)
        got += len(chunk)
    return b"".join(chunks)


def _recv_frame(sock: socket.socket) -> bytes:
    (length,) = _LEN.unpack(_recv_exact(sock, _LEN.size))
    return _recv_exact(sock, length)


class _Ticket:
    """await_pull's return: completes when the staged entry is pulled
    (mirrors the real server's future-style result, which the device plane
    uses to release its staging-admission slot)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._done = False
        self._callbacks = []

    def add_done_callback(self, fn) -> None:
        with self._lock:
            if not self._done:
                self._callbacks.append(fn)
                return
        fn(self)

    def _fire(self) -> None:
        with self._lock:
            if self._done:
                return
            self._done = True
            callbacks, self._callbacks = self._callbacks, []
        for fn in callbacks:
            try:
                fn(self)
            except Exception:  # noqa: BLE001
                pass


class FakeTransferServer:
    def __init__(self, host: str = "127.0.0.1", refuse_pulls: bool = False):
        # uuid -> (host_bytes, shape, dtype_str, ticket)
        self._staged: Dict[int, Tuple[bytes, tuple, str, _Ticket]] = {}
        self._lock = threading.Lock()
        self.refuse_pulls = refuse_pulls
        self.pulls_served = 0
        self._listener = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._listener.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._listener.bind((host, 0))
        self._listener.listen(16)
        self._host, self._port = self._listener.getsockname()
        self._closed = False
        threading.Thread(target=self._accept_loop, name="fake-xfer", daemon=True).start()

    # -- surface consumed by device_plane ---------------------------------
    def address(self) -> str:
        return f"{self._host}:{self._port}"

    def await_pull(self, uuid: int, array) -> _Ticket:
        import numpy as np

        host = np.asarray(array)
        if not host.flags.c_contiguous:
            host = np.ascontiguousarray(host)
        ticket = _Ticket()
        with self._lock:
            self._staged[uuid] = (
                host.reshape(-1).view(np.uint8).tobytes(),
                tuple(host.shape),
                str(host.dtype),
                ticket,
            )
        return ticket

    def connect(self, addr: str) -> "_FakeConnection":
        if self.refuse_pulls:
            raise ConnectionError("fake transfer server configured to refuse pulls")
        return _FakeConnection(addr)

    def close(self) -> None:
        self._closed = True
        try:
            self._listener.close()
        except OSError:
            pass

    # -- server side -------------------------------------------------------
    def staged_count(self) -> int:
        with self._lock:
            return len(self._staged)

    def _accept_loop(self) -> None:
        while not self._closed:
            try:
                sock, _ = self._listener.accept()
            except OSError:
                return
            threading.Thread(
                target=self._serve, args=(sock,), name="fake-xfer-serve", daemon=True
            ).start()

    def _serve(self, sock: socket.socket) -> None:
        try:
            while not self._closed:
                req = pickle.loads(_recv_frame(sock))
                uuid = req["uuid"]
                with self._lock:
                    # one staging per pull: the entry is CONSUMED by its pull
                    entry = self._staged.pop(uuid, None)
                if entry is None:
                    _send_frame(sock, pickle.dumps({"found": False}))
                    continue
                payload, shape, dtype, ticket = entry
                _send_frame(
                    sock,
                    pickle.dumps({"found": True, "shape": shape, "dtype": dtype,
                                  "size": len(payload)}),
                )
                sock.sendall(payload)
                self.pulls_served += 1
                ticket._fire()
        except (ConnectionError, OSError, EOFError, pickle.UnpicklingError):
            pass
        finally:
            try:
                sock.close()
            except OSError:
                pass


class _FakeConnection:
    def __init__(self, addr: str):
        host, _, port = addr.rpartition(":")
        self._sock = socket.create_connection((host or "127.0.0.1", int(port)), timeout=30.0)
        self._lock = threading.Lock()

    def pull(self, uuid: int, template) -> Any:
        import jax
        import numpy as np

        with self._lock:
            _send_frame(self._sock, pickle.dumps({"uuid": uuid}, protocol=5))
            header = pickle.loads(_recv_frame(self._sock))
            if not header.get("found"):
                raise KeyError(f"uuid {uuid} not staged on peer")
            raw = _recv_exact(self._sock, header["size"])
        host = (
            np.frombuffer(raw, dtype=np.uint8)
            .view(np.dtype(header["dtype"]))
            .reshape(header["shape"])
        )
        return jax.device_put(host)
