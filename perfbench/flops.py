"""Operation counts from shapes, and the table of peaks: the arithmetic
behind ``mfu_pct`` and every ``<kernel>_roofline_pct`` lives here and in the
families, where a PR that claims a gain cannot change it.

``train_flops_per_token`` is ``bench.model_flops_per_token`` with one term
corrected: causal attention does half of the ``s x s`` score and value
products (the masked half is never needed), so a layer's attention costs
``2*s*h`` multiply-adds' worth per token forward, not ``4*s*h``. At s1024,
h2048 the difference is 8% of a layer, and the smoke's "~56%" reads ~54%
by this count. Recomputed activations are not counted (model FLOPs only).
"""

from __future__ import annotations

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def train_flops_per_token(*, hidden: int, ffn: int, layers: int,
                          vocab_rows: int, seq: int) -> float:
    """Forward matmul FLOPs per token x3 (backward = 2x forward)."""
    per_layer = (8 * hidden * hidden      # qkv (6 h^2) + out (2 h^2)
                 + 4 * hidden * ffn       # fc1 + fc2
                 + 2 * seq * hidden)      # causal QK^T and PV: half of 4*s*h
    fwd = layers * per_layer + 2 * hidden * vocab_rows   # + tied LM head
    return 3.0 * fwd


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip of ``device_kind``; an unlisted
    kind raises."""
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind.startswith("_") or device_kind not in table:
        raise KeyError(
            f"no published peak for device_kind {device_kind!r}: add it, with "
            f"its source, to perfbench/peaks.json "
            f"(known: {sorted(k for k in table if not k.startswith('_'))})")
    return table[device_kind]


def kernel_floors(trace: dict, counts, device_kind: str) -> dict:
    """``kernel_floor_s.<name>`` for every named kernel of a reduced trace
    (:func:`xplane.reduce`'s ``kernel_calls.<name>``) that ``counts(name)``
    knows: the least seconds the chip could have taken for those calls,
    calls x max(flops / peak FLOP/s, bytes / peak bytes/s). Over
    ``kernel_s.<name>`` it is the kernel's share of its roofline."""
    peak = peaks(device_kind)
    out = {}
    for key, calls in trace.items():
        if not key.startswith("kernel_calls."):
            continue
        name = key[len("kernel_calls."):]
        known = counts(name)
        if known is not None:
            out["kernel_floor_s." + name] = calls * max(
                known[0] / peak["bf16_flops"],
                known[1] / peak["hbm_bytes_per_s"])
    return out
