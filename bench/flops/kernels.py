"""Operations and bytes that the program's kernels need, from their shapes.

Bytes are what the algorithm must move between HBM and the chip, once:
every input read and every result written, at their dtypes.  A kernel
that writes padded lanes or reads a padded operand moves more; that
excess is the kernel's loss, not the algorithm's need.  FLOPs count each
add, multiply, max, exp and log as one.
"""
from __future__ import annotations


def flash_kd_fwd(B: int, V: int, cache_itemsize: int = 2):
    """Forward KD loss with the teacher normalizer given: reads the f32
    student logits, the cached teacher logits and the (B,) teacher lse;
    writes three (B,) row statistics.  Per element: two scalings, a max,
    exp(s - m), a sum, exp(t - lse), t - s, a product and a sum."""
    flops = 11 * B * V
    bytes_ = B * V * (4 + cache_itemsize) + 4 * B + 3 * 4 * B
    return flops, bytes_


def flash_kd_bwd(B: int, V: int, cache_itemsize: int = 2):
    """Gradient wrt the student logits: reads both logit rows and the two
    (B,) lse's; writes the (B, V) f32 gradient.  Per element: two
    scale-and-subtracts, two exps, a difference and a scaling."""
    flops = 8 * B * V
    bytes_ = B * V * (4 + cache_itemsize) + 2 * 4 * B + 4 * B * V
    return flops, bytes_


def group_weighted_average(G: int, N: int, D: int, itemsize: int = 4):
    """Eq. 2 for G groups of N clients over D parameters: reads every
    client's parameters and weight, writes each group's average."""
    flops = 2 * G * N * D
    bytes_ = itemsize * (G * N * D + G * D) + 4 * G * N
    return flops, bytes_
