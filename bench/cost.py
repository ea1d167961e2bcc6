"""Peaks of the chip, and the operations and bytes a call must move.

Every count is worked out from model sizes and the live lengths the harness
knows, never from the program or a compiler's estimate.  Weights and K/V
are bfloat16 (2 bytes) as served.  A multiply-add counts as 2 operations.
"""

from __future__ import annotations

# Google Cloud documentation, "TPU v5e": per chip 197 TFLOP/s bf16,
# 819 GB/s HBM bandwidth, 16 GB HBM.
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}

BYTES = 2  # bfloat16


def peak(kind: str) -> dict:
    """The peaks of a device kind; an unknown kind is an error."""
    if kind not in PEAKS:
        raise KeyError(f"device kind {kind!r} has no entry in the peak table")
    return PEAKS[kind]


def layer_params(m: dict) -> int:
    d, hd, H, KV, F = m["d_model"], m["head_dim"], m["n_heads"], m["n_kv_heads"], m["d_ff"]
    return d * H * hd + 2 * d * KV * hd + H * hd * d + 3 * d * F


def body_params(m: dict) -> int:
    """Parameters every token multiplies through, without the output head."""
    return m["n_layers"] * layer_params(m)


def head_params(m: dict) -> int:
    return m["vocab"] * m["d_model"]


def weight_bytes(m: dict) -> int:
    """Bytes a decode step must read once: every layer and the output head."""
    return (body_params(m) + head_params(m)) * BYTES


def kv_bytes_per_token(m: dict) -> int:
    return 2 * m["n_layers"] * m["n_kv_heads"] * m["head_dim"] * BYTES


def attn_flops(m: dict, keys: int) -> int:
    """Scores and weighted values of one query over ``keys`` keys, all layers."""
    return 4 * m["n_layers"] * m["n_heads"] * m["head_dim"] * keys


def decode_attention(m: dict, lens: list[int]) -> tuple[float, float]:
    """(operations, bytes) of paged decode attention for rows of live
    length ``lens``, all layers: every live key and value read once, each
    row's query read and output written once."""
    L, H, KV, hd = m["n_layers"], m["n_heads"], m["n_kv_heads"], m["head_dim"]
    keys = sum(lens)
    flops = attn_flops(m, keys)
    nbytes = L * (2 * keys * KV * hd + 2 * len(lens) * H * hd) * BYTES
    return float(flops), float(nbytes)


def decode_step(m: dict, lens: list[int]) -> tuple[float, float]:
    """(operations, bytes) of one decode step over the live rows ``lens``."""
    a_flops, a_bytes = decode_attention(m, lens)
    flops = 2 * (body_params(m) + head_params(m)) * len(lens) + a_flops
    nbytes = weight_bytes(m) + a_bytes
    return float(flops), float(nbytes)


def least_time(flops: float, nbytes: float, pk: dict) -> tuple[float, str]:
    """The least seconds the chip could take, and which bound sets it."""
    t_c, t_m = flops / pk["flops"], nbytes / pk["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")


def prompt_flops(m: dict, plen: int) -> float:
    """Model operations of prefilling a prompt: every token through the body
    with causal attention, and the output head for the last position."""
    body = 2 * body_params(m) * plen
    return float(body + attn_flops(m, plen * (plen + 1) // 2) + 2 * head_params(m))


def output_flops(m: dict, keys: int) -> float:
    """Model operations of one decoded token attending over ``keys`` keys."""
    return float(2 * (body_params(m) + head_params(m)) + attn_flops(m, keys))
