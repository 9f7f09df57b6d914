"""HBM roofline share of the one-pass statistics kernel (ef_correct_stats,
or bucket_stats without EF); bytes per element from bench/kernel_bytes.py."""

from bench.kernel_bytes import roofline_pct


def read(ctx):
    return roofline_pct(ctx, r"^(ef_correct_stats|bucket_stats)$")
