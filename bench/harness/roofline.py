"""Operations and compulsory bytes of a kernel's work, from its shapes.

A roofline share is the least time the chip could take, the larger of
operations over the peak rate and bytes over the memory bandwidth, divided
by the kernel's measured time. The counts are of the work the stage must
do, not of how today's kernel does it.
"""
from __future__ import annotations


def adc_scan_work(b: int, n: int, s: int, l: int, pool: int) -> tuple:
    """(ops, bytes) of one 4-bit ADC scan of ``b`` queries over ``n`` rows
    of ``s`` packed subspace codes and ``l`` int32 attributes, keeping a
    pool of ``pool`` candidates a query.

    ops: one lookup-add per query, row and subspace, one compare per
    query, row and attribute. bytes: the codes (s/2 bytes a row), the
    attributes and the per-query LUTs (s x 16 f32) read once, and the
    pool's ids and scores (int32 + f32) written once.
    """
    ops = b * n * s + b * n * l
    read = n * s // 2 + n * l * 4 + b * s * 16 * 4 + b * l * 4
    written = b * pool * 8
    return ops, read + written


def least_seconds(ops: float, nbytes: float, peaks: dict) -> tuple:
    """(seconds, "ops" | "bytes"): the chip's least time and what bounds it."""
    t_ops = ops / peaks["bf16_flop_per_s"]
    t_bytes = nbytes / peaks["hbm_byte_per_s"]
    return (t_ops, "ops") if t_ops >= t_bytes else (t_bytes, "bytes")
