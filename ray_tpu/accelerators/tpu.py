"""TPU detection: pod topology, visible chips, gang resources.

Parity: ``python/ray/_private/accelerators/tpu.py:13-33`` — pod type from
env/metadata, ``TPU_VISIBLE_CHIPS`` masking, per-pod head resource for gang
scheduling, worker count from the hostbounds. GCE metadata calls are
replaced by env inspection + live jax device enumeration (zero egress
means no metadata server).
"""

from __future__ import annotations

import os
import re
from typing import Dict, List, Optional

# env vars the TPU runtime/GKE set on pod VMs (reference constants)
TPU_ACCELERATOR_TYPE_ENV = "TPU_ACCELERATOR_TYPE"     # e.g. "v5litepod-16"
TPU_NAME_ENV = "TPU_NAME"
TPU_WORKER_ID_ENV = "TPU_WORKER_ID"
TPU_VISIBLE_CHIPS_ENV = "TPU_VISIBLE_CHIPS"
TPU_HOST_BOUNDS_ENV = "TPU_HOST_BOUNDS"               # e.g. "2,2,1"

_GENERATION_CHIPS_PER_HOST = {
    "v2": 4, "v3": 4, "v4": 4, "v5litepod": 8, "v5p": 4, "v6e": 8,
}


def get_tpu_pod_type() -> Optional[str]:
    """Normalized pod type, e.g. ``v5litepod-16`` -> ``v5e-16``."""
    raw = os.environ.get(TPU_ACCELERATOR_TYPE_ENV)
    if not raw:
        return None
    return raw.replace("v5litepod", "v5e").replace("v5lite", "v5e")


def get_current_pod_name() -> Optional[str]:
    return os.environ.get(TPU_NAME_ENV) or None


def get_current_pod_worker_count() -> int:
    """Hosts in this pod slice, from TPU_HOST_BOUNDS (product of dims)."""
    bounds = os.environ.get(TPU_HOST_BOUNDS_ENV)
    if not bounds:
        return 1
    count = 1
    for dim in bounds.split(","):
        try:
            count *= max(int(dim), 1)
        except ValueError:
            return 1
    return count


def get_visible_chip_ids() -> Optional[List[int]]:
    """Chip mask from TPU_VISIBLE_CHIPS (None = all visible)."""
    raw = os.environ.get(TPU_VISIBLE_CHIPS_ENV)
    if raw is None or raw == "":
        return None
    try:
        return [int(x) for x in raw.split(",") if x != ""]
    except ValueError:
        return None


def get_chips_per_host(pod_type: Optional[str] = None) -> int:
    """Chips each host of the slice carries: the generation's host size,
    capped by the slice's total chip count (a v5e-4 host has 4, not 8)."""
    pod_type = pod_type or get_tpu_pod_type() or ""
    m = re.match(r"(v\d+[a-z]*|v5litepod|v5e|v5p)", pod_type)
    gen = m.group(1) if m else ""
    gen = {"v5e": "v5litepod"}.get(gen, gen)
    per_host = _GENERATION_CHIPS_PER_HOST.get(gen, 4)
    suffix = pod_type.rsplit("-", 1)[-1]
    try:
        total = int(suffix)
    except ValueError:
        return per_host
    return min(per_host, total) if total > 0 else per_host


def get_num_tpu_chips() -> int:
    """Chips on THIS host. Priority: explicit visible-chip mask, then live
    jax enumeration (jax IS the execution engine — if it sees no TPU,
    advertising chips from env arithmetic would promise capacity tasks can
    never use, e.g. a CPU-forced test process on a TPU VM), then pod-type
    arithmetic only when jax itself is unavailable."""
    visible = get_visible_chip_ids()
    if visible is not None:
        return len(visible)
    try:
        import jax
    except ImportError:
        if get_tpu_pod_type():
            return get_chips_per_host()
        return 0
    try:
        return len([d for d in jax.local_devices() if d.platform != "cpu"])
    except RuntimeError as exc:
        # the backend this process was told to use did not start — the chip
        # is held by another process, or libtpu is broken. Carrying on with
        # "0 chips" would run the whole session on a host that cannot do the
        # work it was started for.
        raise RuntimeError(
            "jax could not start its backend while counting TPU chips. One "
            "process holds a chip at a time: run this one with "
            "JAX_PLATFORMS=cpu if another process on this host owns the "
            "chip, or pass num_tpus= to rt.init() to skip detection."
        ) from exc


def tpu_head_resource_name(pod_type: str) -> str:
    """The gang-scheduling token placed on worker 0 of a pod slice
    (reference "TPU-<pod_type>-head", tpu.py:28)."""
    return f"TPU-{pod_type}-head"


def tpu_pod_resources() -> Dict[str, float]:
    """The resource dict this host should register (reference: resources
    auto-filled at node start): chip count, plus the pod head token when
    this is worker 0 of a multi-host slice."""
    out: Dict[str, float] = {}
    chips = get_num_tpu_chips()
    if not chips:
        # no usable chips on this host: don't advertise the head token
        # either, or gang tasks would land somewhere TPU work can't run
        return out
    out["TPU"] = float(chips)
    pod_type = get_tpu_pod_type()
    if pod_type and os.environ.get(TPU_WORKER_ID_ENV, "0") == "0":
        out[tpu_head_resource_name(pod_type)] = 1.0
    return out
