"""What the readers of latent attention's per-layer metrics share: how its
two kernels are told in a trace, and the cached tokens a decode step reads.
An event carries the instruction, the opcode and the result shape; the sizes
come from the configuration file's published keys alone."""

from __future__ import annotations

import re
from typing import Any, Callable, Dict, Optional

DECODE, PREFILL = "jit__decode_k_paged", "jit__prefill_chunk"


def _layers(c: Dict[str, Any]) -> int:
    lac = c.get("linear_attn_config") or {}
    return sum(1 for i in lac.get("full_attn_layers", ()) if i <= c.get("num_hidden_layers", 0)) if c.get("kv_lora_rank") else 0


def _custom_call(shape: str) -> Callable[[str], bool]:
    pattern = re.compile(rf" custom-call \w+\[{shape}\]")
    return lambda name: bool(pattern.search(name))


def decode_kernel(c: Dict[str, Any]) -> Optional[Callable[[str], bool]]:
    """A predicate on an operation's (short) name: the latent decode kernel,
    a custom-call whose result is ``[slots, heads (to a sublane tile of 16),
    kv_lora_rank]``. None for a configuration without latent attention."""
    if not _layers(c) or "run" not in c:
        return None
    heads = -(-c["num_attention_heads"] // 16) * 16
    return _custom_call(f"{c['run']['max_batch_size']},{heads},{c['kv_lora_rank']}")


def prefill_kernel(c: Dict[str, Any]) -> Optional[Callable[[str], bool]]:
    """The latent prefill kernel: ``[1, chunk tokens x heads, kv_lora_rank]``."""
    if not _layers(c) or "run" not in c:
        return None
    return _custom_call(f"1,{c['run']['prefill_chunk_tokens'] * c['num_attention_heads']},{c['kv_lora_rank']}")


def live_tokens(c: Dict[str, Any], kv_live_pages: float, rows: float) -> float:
    """Cached tokens a decode step's latent attention reads in ONE latent
    layer, summed over the live rows: ``kv_live_pages`` is the pages visited
    in a layer averaged over ALL layers (only the latent layers visit any),
    a row's last page is half full in the mean."""
    bs = c["run"]["kv_block_size"]
    pages = kv_live_pages * c["num_hidden_layers"] / _layers(c)
    return max(0.0, pages * bs - rows * bs / 2)
