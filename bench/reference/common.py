"""Numerics shared by the plain references: matmul precision, RMSNorm, the optimizer.

Every contraction goes through :func:`dot`.  At ``"highest"`` it is a
float32 einsum under ``jax.default_matmul_precision("highest")`` (the
caller traces inside that context).  At ``"fp8"`` each operand is first
rounded to float8 e4m3 with a per-tensor scale (amax mapped to the e4m3
maximum), and in the backward pass each cotangent to float8 e5m2 likewise:
the step below the configurations' bfloat16 that a later change might be
tempted to take.  This is the control that has to fail the comparison.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

E4M3_MAX = 448.0


E5M2_MAX = 57344.0


def _scaled_round(x: jax.Array, dtype, top: float) -> jax.Array:
    x = x.astype(jnp.float32)
    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    scale = top / amax
    return (x * scale).astype(dtype).astype(jnp.float32) / scale


@jax.custom_vjp
def fp8_round(x: jax.Array) -> jax.Array:
    """Per-tensor scaled e4m3 rounding; its cotangent is rounded to scaled e5m2,
    as float8 training rounds gradients."""
    return _scaled_round(x, jnp.float8_e4m3fn, E4M3_MAX)


def _fp8_fwd(x):
    return fp8_round(x), None


def _fp8_bwd(_, g):
    return (_scaled_round(g, jnp.float8_e5m2, E5M2_MAX),)


fp8_round.defvjp(_fp8_fwd, _fp8_bwd)


def dot(precision: str, spec: str, *xs: jax.Array) -> jax.Array:
    xs = [x.astype(jnp.float32) for x in xs]
    if precision == "fp8":
        xs = [fp8_round(x) for x in xs]
    return jnp.einsum(spec, *xs, precision=jax.lax.Precision.HIGHEST)


def rmsnorm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    """x / rms(x) * (1 + scale): the (1 + scale) parameterisation the configs state."""
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + scale)


def momentum_sgd(opt: dict):
    """Heavy-ball SGD with decoupled-from-nothing L2 decay on matrices.

    ``update(p, g, m, ndim, dtype) -> (p', m')``: g += wd * p where the
    stored leaf has more than one dimension, m' = momentum * m + g,
    p' = p - lr * m'; both results are stored at the leaf's dtype (the
    configuration keeps parameters and momentum in bfloat16), as the
    program's state would hold them.
    """
    lr, mom, wd = float(opt["lr"]), float(opt["momentum"]), float(opt["weight_decay"])

    def update(p, g, m, ndim: int, dtype):
        p32 = p.astype(jnp.float32)
        g32 = g.astype(jnp.float32)
        if wd and ndim > 1:
            g32 = g32 + wd * p32
        m_new = mom * m.astype(jnp.float32) + g32
        p_new = p32 - lr * m_new
        return p_new.astype(dtype), m_new.astype(dtype)

    def grad_from_state(m1, p0, ndim: int):
        """The first step's gradient, read back from the momentum after it."""
        g = m1.astype(jnp.float32)
        if wd and ndim > 1:
            g = g - wd * p0.astype(jnp.float32)
        return g

    return update, grad_from_state


OPTIMIZERS = {"momentum_sgd": momentum_sgd}
