"""Chip peaks and the operations and bytes a computation needs.

The peaks are the published ones, keyed by ``device_kind`` as JAX reports
it; a device that is not in the table is an error, never a default.
The counting helpers take shapes only, so the benchmark, not the
program, decides what a kernel or a step had to do; each family's
``reference.py`` adds up its own layers with them.
"""
from __future__ import annotations

# Google Cloud documentation, "TPU v5e" (system architecture page):
# 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2 at 819 GB/s per chip.
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add them to bench/roofline.py "
                       f"with their source") from None


def min_seconds(ops: float, nbytes: float, pk: dict,
                ops_peak: str = "int8_ops") -> float:
    """The least time the chip could take: the larger of operations over
    peak and bytes over HBM bandwidth."""
    return max(ops / pk[ops_peak], nbytes / pk["hbm_bytes_per_s"])


def decode_attn_cost(contexts, q_heads: int, kv_heads: int, head_dim: int,
                     window: int = 0) -> tuple[float, float]:
    """One layer's single-query attention over int8 K/V with one f32 scale
    per token and head, and one int32 position per token: for each lane,
    its context length, or ``window`` keys where the layer's window is
    shorter (0: no window).  Query and output in bf16.  (ops, bytes)
    summed over lanes."""
    ops = nbytes = 0.0
    for c in contexts:
        c = min(c, window) if window else c
        ops += 4.0 * c * head_dim * q_heads
        nbytes += c * (kv_heads * (2 * head_dim + 2 * 4) + 4)
        nbytes += 2 * 2 * q_heads * head_dim
    return ops, nbytes


def span_keys(p0: int, c: int, window: int = 0) -> float:
    """Keys that ``c`` causal queries at positions p0 .. p0 + c - 1 attend
    together in one layer: p + 1 at position p, or ``window`` where the
    layer's window is shorter (0: no window)."""
    # the first m queries' contexts fit inside the window
    m = c if not window else max(0, min(c, window - p0))
    return m * p0 + m * (m + 1) / 2 + (c - m) * window
