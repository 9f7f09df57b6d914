"""HBM bytes each codec kernel's algorithm needs per element of its bucket.

What the algorithm has to move, not what the implementation sweeps:

- ``ef_correct_stats``: read the gradient and the EF residual, write the
  corrected bucket: 12 B (float32 each);
- ``bucket_stats`` (no EF): read the gradient: 4 B;
- ``*_encode_pack_residual``: read the corrected bucket, write the new
  residual and the bit-packed payload: 8 B + bits/8;
- ``*_encode_pack`` (words only): read the bucket, write the payload:
  4 B + bits/8.

The random draw the stochastic rounding reads is not counted: the
algorithm needs random bits, not a bucket-sized array of them in HBM.
"""
from __future__ import annotations

import re

RULES = (
    (r"^ef_correct_stats$", lambda bits: 12.0),
    (r"^bucket_stats$", lambda bits: 4.0),
    (r"^(codebook|uniform)_encode_pack_residual$", lambda bits: 8.0 + bits / 8.0),
    (r"^(codebook|uniform)_encode_pack$", lambda bits: 4.0 + bits / 8.0),
)


def bytes_per_element(kernel: str, bits: int) -> float | None:
    """Bytes per element for a kernel (named after its jitted wrapper), or None."""
    for pattern, fn in RULES:
        if re.match(pattern, kernel):
            return fn(bits)
    return None


def elements(operand_elements: list) -> int:
    """A call's bucket elements: its largest operand (the (rows, 128) float32 tile)."""
    return max(operand_elements) if operand_elements else 0


def roofline_pct(ctx, pattern: str) -> float | None:
    """Share of the HBM roofline the matching kernels reach over the trace.

    The least time is the bytes the algorithm needs (elements x bytes per
    element, summed over every call in the trace) at the chip's HBM peak;
    the share is that over the calls' summed device time.  None when the
    trace holds no such call.
    """
    bits = int(ctx.workload.get("bits", 3))
    need, spent = 0.0, 0.0
    for e in ctx.kernel_events(pattern):
        bpe = bytes_per_element(e.op.kernel, bits)
        if bpe is None:
            continue
        need += bpe * elements(e.op.operand_elements)
        spent += e.dur_ns * 1e-9
    if spent <= 0.0:
        return None
    return 100.0 * (need / ctx.peaks["hbm_bytes_per_s"]) / spent
