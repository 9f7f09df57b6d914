"""HBM roofline share of the fused encode + bit-pack kernels (with and
without the residual); bytes per element from bench/kernel_bytes.py."""

from bench.kernel_bytes import roofline_pct


def read(ctx):
    return roofline_pct(ctx, r"_encode_pack(_residual)?$")
