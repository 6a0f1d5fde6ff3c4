"""Operations and bytes a kernel's algorithm needs, from its shapes: the
numerators of the roofline shares. Kept with the benchmark so that a PR that
changes a kernel cannot change what it is measured against."""

from __future__ import annotations


def flash_forward_cost(batch: int, heads: int, seq_len: int, head_dim: int,
                       bytes_per_element: int = 2) -> tuple[float, float]:
    """(FLOPs, bytes) of one causal attention forward over [B, H, T, D]:
    QK^T and PV are 2 T^2 D multiply-adds each a head, of which causality
    needs half; q, k and v are read and the output written once."""
    flops = 2.0 * seq_len * seq_len * head_dim * batch * heads
    bytes_moved = 4.0 * batch * heads * seq_len * head_dim * bytes_per_element
    return flops, bytes_moved


def least_seconds(flops: float, bytes_moved: float, peaks: dict) -> tuple[float, str]:
    """The least time the chip could take, and which peak bounds it."""
    by_compute = flops / peaks["bf16_flops_per_s"]
    by_memory = bytes_moved / peaks["hbm_bytes_per_s"]
    return (by_compute, "compute") if by_compute >= by_memory else (by_memory, "memory")
