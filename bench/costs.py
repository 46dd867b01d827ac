"""The yardstick's arithmetic: peaks by device kind, and the operations
and bytes of the two Pallas kernels of the fused decode round, from
their shapes.

Bytes are each operand read once and each output written once, as the
kernel is called (its padded columns included): the least traffic the
call needs. Operations are the multiply-adds of the T data and r parity
GEMMs the kernel runs (2 per multiply-add).
"""
from __future__ import annotations

import json
import os

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
LANE = 128


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; an unknown kind is an
    error, never a default."""
    with open(os.path.join(BENCH_DIR, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json")
    return table[device_kind]


def coded_matmul(rows: int, k: int, m_l: int, t: int, r: int,
                 x_bytes: int = 2, w_bytes: int = 2, parity_bytes: int = 4,
                 out_bytes: int = 2) -> tuple[float, float]:
    """(operations, bytes) of one ``cdc_coded_matmul_pallas`` call:
    x [rows, k] @ w_shards [t, k, m_l] and parity_w [r, k, m_l] ->
    merged [rows, t, m_l], with the [m_l] equation plan (int32 + f32)."""
    ops = 2.0 * rows * k * m_l * (t + r)
    nbytes = (rows * k * x_bytes + t * k * m_l * w_bytes
              + r * k * m_l * parity_bytes + rows * t * m_l * out_bytes
              + 2 * m_l * 4)
    return ops, float(nbytes)


def fused_head(rows: int, k: int, cols: int, t: int, x_bytes: int = 4,
               w_bytes: int = 2, parity_bytes: int = 4
               ) -> tuple[float, float]:
    """(operations, bytes) of one ``cdc_fused_head_argmax_pallas`` call:
    x [rows, k] @ w_shards [t, k, cols] and the sum parity [k, cols] ->
    (max logit, token) per row."""
    ops = 2.0 * rows * k * cols * (t + 1)
    nbytes = rows * k * x_bytes + t * k * cols * w_bytes \
        + k * cols * parity_bytes + rows * 8
    return ops, float(nbytes)


def pad(m: int, q: int) -> int:
    return -(-m // q) * q


def round_kernel_calls(hf: dict, rows: int, t: int, r: int) -> dict:
    """Per decode round of a dense GQA model: the (operations, bytes) of
    every call of each kernel, as a list per kernel name."""
    from bench.models.llama_gqa import dims
    m = dims(hf)
    q = t * t
    widths = [m["h"] * m["hd"], m["hkv"] * m["hd"], m["hkv"] * m["hd"],
              m["f"], m["f"]]                      # wq wk wv w_gate w_up
    per_layer = [coded_matmul(rows, m["d"], pad(w, q) // t, t, r)
                 for w in widths]
    cols = pad(pad(m["v"], q) // t, LANE)
    return {"cdc_coded_matmul_pallas": per_layer * m["L"],
            "cdc_fused_head_argmax_pallas": [fused_head(rows, m["d"], cols,
                                                        t)]}


def min_seconds(calls: list, pk: dict) -> tuple[float, str]:
    """Least time the chip needs for ``calls`` [(ops, bytes)], and which
    bound sets it in most of the calls."""
    total, by_mem = 0.0, 0
    for ops, nbytes in calls:
        tc, tm = ops / pk["bf16_flops"], nbytes / pk["hbm_bytes_s"]
        total += max(tc, tm)
        by_mem += tm >= tc
    return total, ("memory" if 2 * by_mem >= len(calls) else "compute")
