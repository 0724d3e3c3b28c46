"""Required work, counted at the logical shapes of what the algorithm needs:
no padding, no recomputation, no redundant forward.  Shared by the roofline
and utilisation readers of this directory."""

from __future__ import annotations

import numpy as np

F32 = 4  # bytes


def lm_matmul_params(m: dict) -> int:
    """Parameters a token's forward multiplies by: every layer's projections
    and MLP, the output head and the value head (embedding lookups excluded)."""
    d, H, KV, hd, F = m["d_model"], m["num_heads"], m["num_kv_heads"], m["head_dim"], m["d_ff"]
    per_layer = d * H * hd + 2 * d * KV * hd + H * hd * d + 3 * d * F
    sizes = (d, *m["value_head"], 1)
    value = sum(a * b for a, b in zip(sizes[:-1], sizes[1:]))
    return m["num_layers"] * per_layer + d * m["vocab_size"] + value


def lm_token_forward_flops(m: dict, length) -> np.ndarray:
    """FLOPs of generating one token whose sequence holds ``length`` tokens:
    the matmuls, plus attention of the newest position over ``length``."""
    length = np.asarray(length, np.float64)
    attn = 4.0 * m["num_heads"] * m["head_dim"] * length * m["num_layers"]
    return 2.0 * lm_matmul_params(m) + attn


def causal_attention(m: dict, length) -> tuple:
    """(FLOPs, bytes) of causal attention over each row's valid ``length``
    (all layers): QK^T and PV over the lower triangle; q, k, v, out read or
    written once."""
    L = np.asarray(length, np.float64)
    H, KV, D, n = m["num_heads"], m["num_kv_heads"], m["head_dim"], m["num_layers"]
    flops = 2.0 * H * D * L * (L + 1) * n
    byts = (2 * H + 2 * KV) * D * L * F32 * n
    return float(np.sum(flops)), float(np.sum(byts))


def decode_attention(m: dict, length) -> tuple:
    """(FLOPs, bytes) of one decode step per row over its ``length`` cached
    positions (all layers): the query, the valid K and V prefix and the
    output, each read or written once, wherever the program keeps them."""
    L = np.asarray(length, np.float64)
    H, KV, D, n = m["num_heads"], m["num_kv_heads"], m["head_dim"], m["num_layers"]
    flops = 4.0 * H * D * L * n
    byts = (2 * KV * D * L + 2 * H * D) * F32 * n
    return float(np.sum(flops)), float(np.sum(byts))


def mlp_forward_flops(m: dict) -> float:
    """FLOPs of one observation through both actor-critic towers."""
    hid = list(m["hidden"])
    pi = [m["obs_dim"], *hid, m["num_actions"]]
    vf = [m["obs_dim"], *hid, 1]
    return 2.0 * sum(a * b for s in (pi, vf) for a, b in zip(s[:-1], s[1:]))


def roofline_pct(flops: float, byts: float, seconds: float, peak: dict) -> float:
    """Least time the chip could take (the larger of the compute and the
    memory bound) over the measured time, in percent."""
    least = max(flops / peak["bf16_flops"], byts / peak["hbm_bytes_per_s"])
    return 100.0 * least / seconds


def rows(facts: dict):
    """Concatenated per-row stats the samplers returned in the window."""
    rs = facts.get("rows") or []
    if not rs or "length" not in rs[0]:
        return None
    return {k: np.concatenate([r[k] for r in rs]) for k in rs[0]}
