"""Operations and bytes of one fleet route call, from shapes alone.

The work is what `kernels/ref.fleet_route` defines for B tasks over M
servers, K = depth + 2 tiers and `depth` hierarchy levels:

per server (M of them), the workload
    K divisions and K - 1 additions of the tier sum,
    1 comparison, 1 division and 1 selection for the in-service residual,
    1 addition of it                                   -> 2K + 3

per (task, server) pair (B * M of them)
    locality:   3 comparisons and 2 ors                -> 5
    each level: 3 comparisons, 2 ors, 2 selections     -> 7 per level
    local override of tier and rate: 2 selections      -> 2
    score W / rate - rate * 1e-6: 1 div, 1 mul, 1 sub  -> 3
    private mask: 1 comparison, 1 selection            -> 2
    minimum: 1                                         -> 1
    lowest index at the minimum: 1 comparison, 1 selection, 1 minimum -> 3
    tier at that index: 1 comparison, 1 selection, 1 minimum          -> 3
                                                       -> 19 + 7 * depth

Bytes are each input read once and each output written once, 4 bytes an
element: q and the rates (M, K), serving (M,), the ancestor table
(depth, M), the task table (B, 3 (depth + 1)) and three (B,) results.

The least time is the larger of operations over the chip's bf16 peak and
bytes over its HBM bandwidth; `bound` names which of the two it is.
"""

from __future__ import annotations


def ops(b: int, m: int, k: int, depth: int) -> int:
    return m * (2 * k + 3) + b * m * (19 + 7 * depth)


def bytes_moved(b: int, m: int, k: int, depth: int) -> int:
    elems = 2 * m * k + m + depth * m + 3 * b * (depth + 1) + 3 * b
    return 4 * elems


def least_time(shape: dict, peak: dict) -> tuple[float, str]:
    """(seconds, "ops" or "bytes") for shape {b, m, k, depth}."""
    t_ops = ops(**shape) / peak["bf16_flops_per_s"]
    t_bytes = bytes_moved(**shape) / peak["hbm_bytes_per_s"]
    return (t_ops, "ops") if t_ops >= t_bytes else (t_bytes, "bytes")
