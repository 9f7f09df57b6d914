"""Compile every main-path Pallas kernel for a TPU v5e without the chip.

The interpret-mode kernel tests check numbers on the CPU; they cannot see
what the TPU compiler (Mosaic) refuses: unaligned block shapes, gathers,
float iotas.  Each case here lowers one ``kernels.ops`` wrapper that the
bucketed codec reaches under ``use_pallas=True`` with ``interpret=False``,
at the real 4 MiB bucket size (2^20 fp32 elements, 3 bits; 1 and 4 peers
for the decode kernels), compiles it for one chip of a described ``v5e:2x2``
topology, and checks that the kernel (``tpu_custom_call``) is in the
compiled program.  Nothing runs; the compiles take about a second each.

The topology is described inside a module-scoped fixture, never at import
or collection time: only one process at a time may load the TPU compiler's
library, and every test worker imports this file.  The persistent
compilation cache is off around these compiles — an entry written for a
described chip cannot be read back without one.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.quantizers import packed_size
from repro.kernels import ops

N = 1 << 20        # elements of a 4 MiB fp32 bucket
BITS = 3           # the paper default
S = 2**BITS - 1

ENCODE = ["uniform_encode_pack", "codebook_encode_pack",
          "uniform_encode_pack_residual", "codebook_encode_pack_residual",
          "bucket_stats", "ef_correct_stats", "orthogonalize"]
DECODE = [f"{kind}_{op}-p{peers}" for kind in ("uniform", "codebook")
          for op in ("decode_reduce", "decode_rows") for peers in (1, 4)]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # any failure means no TPU compiler is usable here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _call(name: str, one_chip):
    """(wrapper, leading traced operands as shapes, trailing static args)."""
    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    g, key = sds((N,)), sds((2,), jnp.uint32)
    if name in ENCODE:
        fn = getattr(ops, name)
        if name == "bucket_stats":
            return fn, (g,), ()
        if name == "ef_correct_stats":
            return fn, (g, g), ()
        if name == "orthogonalize":
            return fn, (sds((1024, 4)),), ()
        meta = sds(()) if name.startswith("uniform") else sds((S + 1,))
        return lambda g, m, k, interpret: fn(g, m, BITS, k, interpret=interpret), \
            (g, meta, key), ()
    op, peers = name.rsplit("-p", 1)
    peers = int(peers)
    words = sds((peers, packed_size(N, BITS)), jnp.uint32)
    meta = sds((peers,)) if op.startswith("uniform") else sds((peers, S + 1))
    return getattr(ops, op), (words, meta), (N, BITS)


@pytest.mark.parametrize("name", ENCODE + DECODE)
def test_kernel_compiles_for_v5e(name, one_chip, no_compile_cache):
    fn, traced, static = _call(name, one_chip)

    def run(*xs):
        return fn(*xs, *static, interpret=False)

    compiled = jax.jit(run).lower(*traced).compile()
    assert "tpu_custom_call" in compiled.as_text(), name
