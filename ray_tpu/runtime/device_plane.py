"""Device-array movement across the fabric (SURVEY §5.8's core demand).

Replaces the round-2 behavior where a ``jax.Array`` crossing processes was
``device_get`` → **in-band pickle** → TCP via the head → unpickle: device
arrays now travel in a device-aware envelope —

  * serialization (``DevicePickler.reducer_override``): a concrete
    ``jax.Array`` reduces to (shape, dtype, ``PickleBuffer`` of its host
    view).  Under the data plane's pickle-5 out-of-band framing the buffer
    streams RAW (sendall/recv_into, GIL released) — array bytes never enter
    a pickle stream, and the consumer rebuilds a real device array with
    ``jax.device_put``, not a numpy imposter.
  * placement: producers tag device-resident objects in the head's object
    directory (``object_location``/lazy-commit metadata) so consumers and
    the state API know where device copies live.
  * ICI/DCN: when both endpoints run a ``jax.experimental.transfer`` server
    (real multi-host TPU; the role NCCL channels play for GPUs in the
    reference — ``python/ray/experimental/channel/nccl_group.py:18``), the
    pull goes device-to-device through that server and the host envelope is
    skipped.  Probed lazily; backends without support (CPU, a single
    process) fall back to the envelope transparently.

Reference anchors: ``src/ray/object_manager/object_manager.h:117`` (the
role being replaced), ``python/ray/experimental/channel/nccl_group.py:18``.
"""

from __future__ import annotations

import io
import pickle
import threading
from typing import Any, Optional, Tuple


class DeviceStats:
    def __init__(self):
        self._lock = threading.Lock()
        self.arrays_packed = 0     # device arrays serialized via the envelope
        self.arrays_restored = 0   # device arrays rebuilt with device_put
        self.bytes_moved = 0
        self.ici_pulls = 0         # transfers that rode the jax transfer server

    def add(self, field: str, n: int = 1) -> None:
        with self._lock:
            setattr(self, field, getattr(self, field) + n)

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "arrays_packed": self.arrays_packed,
                "arrays_restored": self.arrays_restored,
                "bytes_moved": self.bytes_moved,
                "ici_pulls": self.ici_pulls,
            }


stats = DeviceStats()


def _jax_array_type():
    try:
        import jax

        return jax.Array
    except Exception:  # noqa: BLE001 — jax absent in some tool contexts
        return ()


def is_device_array(value: Any) -> bool:
    """Concrete, fully-addressable (non-tracer) jax.Array?"""
    jax_array = _jax_array_type()
    if not jax_array or not isinstance(value, jax_array):
        return False
    try:
        from jax.core import Tracer

        if isinstance(value, Tracer):
            return False  # abstract value inside a trace: no buffers
    except ImportError:
        pass
    # cross-host global arrays can't be exported from one process
    return bool(getattr(value, "is_fully_addressable", True))


def _rebuild_device_array(shape, dtype_str, buf):
    """Unpickle hook: raw host buffer -> device-resident jax.Array.  The
    buffer is a uint8 view (TPU dtypes like bfloat16 reject the buffer
    protocol directly); reinterpret then device_put."""
    import jax
    import numpy as np

    host = np.frombuffer(buf, dtype=np.uint8).view(np.dtype(dtype_str)).reshape(shape)
    arr = jax.device_put(host)
    stats.add("arrays_restored")
    stats.add("bytes_moved", host.nbytes)
    return arr


class _DeviceReducerMixin:
    """reducer_override shared by the pickle and cloudpickle paths."""

    def reducer_override(self, obj):
        if is_device_array(obj):
            import numpy as np

            host = np.asarray(obj)  # device->host export; zero-copy on CPU
            if not host.flags.c_contiguous:
                host = np.ascontiguousarray(host)
            stats.add("arrays_packed")
            # uint8 view: TPU dtypes (bfloat16 etc.) reject the buffer
            # protocol; the raw bytes stream identically either way
            raw = host.reshape(-1).view(np.uint8)
            return (
                _rebuild_device_array,
                (host.shape, str(host.dtype), pickle.PickleBuffer(raw)),
            )
        return NotImplemented


class DevicePickler(_DeviceReducerMixin, pickle.Pickler):
    pass


def dumps_with_device_envelope(value: Any, buffer_callback) -> bytes:
    """pickle-5 dump routing concrete jax.Arrays through the device
    envelope (buffers out-of-band).  cloudpickle fallback keeps the same
    reducer via its own pickler subclass.  Buffers reach the caller only
    from the attempt that SUCCEEDS (a half-failed pass must not leak)."""
    collected: list = []
    out = io.BytesIO()
    try:
        DevicePickler(out, protocol=5, buffer_callback=collected.append).dump(value)
    except (AttributeError, TypeError, pickle.PicklingError):
        import cloudpickle

        class _DeviceCloudPickler(_DeviceReducerMixin, cloudpickle.CloudPickler):
            def reducer_override(self, obj):
                r = _DeviceReducerMixin.reducer_override(self, obj)
                if r is not NotImplemented:
                    return r
                return super().reducer_override(obj)

        collected.clear()
        out = io.BytesIO()
        _DeviceCloudPickler(out, protocol=5, buffer_callback=collected.append).dump(value)
    for b in collected:
        buffer_callback(b)
    return out.getvalue()


# --------------------------------------------------------------------------
# ICI/DCN device-to-device path (jax.experimental.transfer)
# --------------------------------------------------------------------------
_xfer_lock = threading.Lock()
_xfer_server = None
_xfer_probed = False


def install_transfer_server(server: Optional[Any]) -> None:
    """Inject a transfer server (tests / the fake): subsequent
    ``transfer_server()`` calls return it without probing the platform.
    Pass None to reset to the unprobed state."""
    global _xfer_server, _xfer_probed, _staged_outstanding
    with _xfer_lock:
        _xfer_server = server
        _xfer_probed = server is not None
    # staged entries belong to the outgoing server; its replacement (or
    # removal) invalidates them, so the admission counter resets with it
    with _staged_lock:
        _staged_outstanding = 0


def transfer_server() -> Optional[Any]:
    """This process's jax transfer server, enabled ONLY on real multi-host
    TPU backends.  The gate is a platform check, not a construction probe:
    the CPU backend happily constructs a server and then hard-CRASHES the
    process (fatal ``Check failed`` in streaming.cc) on first pull — an
    unservable backend must never advertise device transfer.

    ``RAY_TPU_FAKE_DEVICE_TRANSFER=1`` substitutes the host-memory-backed
    fake (``runtime/fake_transfer.py``) so the negotiation protocol runs
    end-to-end on any backend — the dryrun and tests prove the offer →
    ticket → pull → release path itself, not just the probe."""
    global _xfer_server, _xfer_probed
    with _xfer_lock:
        if _xfer_probed:
            return _xfer_server
        _xfer_probed = True
        _xfer_server = None
        import os

        if os.environ.get("RAY_TPU_FAKE_DEVICE_TRANSFER"):
            from ray_tpu.runtime.fake_transfer import FakeTransferServer

            _xfer_server = FakeTransferServer()
            return _xfer_server
        try:
            import jax

            from ray_tpu.ops import backend

            if not backend.on_tpu() or jax.process_count() < 2:
                return None
            from jax.experimental import transfer as jxt

            server = jxt.start_transfer_server(jax.local_devices()[0].client)
            server.address()
            _xfer_server = server
        except Exception:  # noqa: BLE001 — unsupported backend
            _xfer_server = None
        return _xfer_server


def transfer_address() -> Optional[str]:
    server = transfer_server()
    if server is None:
        return None
    try:
        return server.address()
    except Exception:  # noqa: BLE001
        return None


_staged_lock = threading.Lock()
_staged_outstanding = 0
_STAGED_CAP = 256


def offer_device_pull(uuid: int, array) -> bool:
    """Producer side: stage a device array for a device-to-device pull
    (one staging per pull — multiple consumers each stage their own).
    Returns False when the backend can't serve (caller uses the envelope).

    Caveat: jax.experimental.transfer has no cancel API, so a consumer that
    fails mid-pull and falls back to the host envelope leaves its staging
    entry pinned.  A hard cap bounds the worst case: past it we stop
    offering and every pull takes the envelope path (correct, just slower)."""
    global _staged_outstanding
    server = transfer_server()
    if server is None:
        return False
    with _staged_lock:
        if _staged_outstanding >= _STAGED_CAP:
            return False
    try:
        res = server.await_pull(uuid, array)
        with _staged_lock:
            _staged_outstanding += 1

        def _release():
            global _staged_outstanding
            with _staged_lock:
                _staged_outstanding = max(0, _staged_outstanding - 1)

        # release the admission slot when the pull completes (future-style
        # result) or after a generous TTL (no cancel/observe API otherwise)
        if hasattr(res, "add_done_callback"):
            res.add_done_callback(lambda _f: _release())
        else:
            t = threading.Timer(300.0, _release)
            t.daemon = True
            t.start()
        return True
    except Exception:  # noqa: BLE001
        return False


def device_pull(addr: str, uuid: int, template) -> Optional[Any]:
    """Consumer side: pull a staged device array directly device-to-device.
    ``template`` is an aval-compatible array/ShapeDtypeStruct.  None when
    the local backend can't participate."""
    server = transfer_server()
    if server is None:
        return None
    try:
        conn = server.connect(addr)
        out = conn.pull(uuid, template)
        stats.add("ici_pulls")
        return out
    except Exception:  # noqa: BLE001
        return None


def uuid_for_object(oid_bytes: bytes) -> int:
    """Stable transfer-uuid for an ObjectID (both ends derive it)."""
    return int.from_bytes(oid_bytes[:8], "little") or 1
