"""Jaxpr/VMEM regression fixtures for ``repro.analysis``.

Loaded by path from ``tests/test_analysis.py`` (never on ``sys.path``).
Each builder returns a ClosedJaxpr that must trip exactly the rule named
in the corpus README; the builders trace on whatever devices exist — a
single-device ``("data",)`` mesh still binds ``axis_index`` and the
collective primitives, which is all the linter inspects.
"""
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def _mesh():
    return jax.make_mesh((1,), ("data",))


def _smap(body, in_specs, out_specs):
    return jax.jit(jax.shard_map(
        body, mesh=_mesh(), in_specs=in_specs, out_specs=out_specs, check_vma=False))


def correlated_rng():
    """REPRO102: every peer folds the same constant — identical noise."""
    def body(key, x):
        k = jax.random.fold_in(key, 7)  # no axis_index in the fold
        return x + jax.random.uniform(k, x.shape)

    key = jax.random.key(0)  # repro: allow REPRO204 (fixture trace input)
    x = jnp.zeros((8, 4), jnp.float32)
    return _smap(body, (P(), P("data")), P("data")).trace(key, x).jaxpr


def decorrelated_rng():
    """The fixed variant (the PR 2 pattern): fold the axis index in."""
    def body(key, x):
        k = jax.random.fold_in(key, jax.lax.axis_index("data"))
        return x + jax.random.uniform(k, x.shape)

    key = jax.random.key(0)  # repro: allow REPRO204 (fixture trace input)
    x = jnp.zeros((8, 4), jnp.float32)
    return _smap(body, (P(), P("data")), P("data")).trace(key, x).jaxpr


def extra_collective():
    """REPRO101 against a budget of 1: a second, redundant all-gather."""
    def body(x):
        g = jax.lax.all_gather(x, "data")
        h = jax.lax.all_gather(x * 2.0, "data")  # the extra hop
        return (g + h).reshape(-1)

    x = jnp.zeros((8,), jnp.float32)
    return _smap(body, (P("data"),), P()).trace(x).jaxpr


def f64_leak():
    """REPRO103: a float64 value escaping into the traced computation."""
    def f(x):
        return jnp.sum(x.astype(jnp.float64))

    with jax.enable_x64(True):
        return jax.jit(f).trace(jnp.zeros((8,), jnp.float32)).jaxpr


def scatter_add():
    """REPRO104: float scatter-add with potentially colliding indices."""
    def f(idx, v):
        return jnp.zeros((8,), jnp.float32).at[idx].add(v)

    idx = jnp.zeros((16,), jnp.int32)
    v = jnp.ones((16,), jnp.float32)
    return jax.jit(f).trace(idx, v).jaxpr


def suppressed_scatter_add():
    """The same scatter-add carrying the allow comment: the finding is
    anchored to this source line, so the comment silences it."""
    def f(idx, v):
        # repro: allow REPRO104 (fixture: a documented, order-free scatter)
        return jnp.zeros((8,), jnp.float32).at[idx].add(v)

    idx = jnp.zeros((16,), jnp.int32)
    v = jnp.ones((16,), jnp.float32)
    return jax.jit(f).trace(idx, v).jaxpr


def wire_f32():
    """REPRO105: fp32 rows on a compressed-wire collective (the codec
    contract is one uint32 word vector per bucket)."""
    def body(x):
        return jax.lax.all_gather(x, "data").reshape(-1)

    x = jnp.zeros((8,), jnp.float32)
    return _smap(body, (P("data"),), P()).trace(x).jaxpr


def vmem_blowout_thunk():
    """REPRO301: a (4096, 8192) fp32 double-buffered block — 256 MiB of
    VMEM against the 4 MiB default budget."""
    from jax.experimental import pallas as pl

    def copy_kernel(x_ref, o_ref):
        o_ref[...] = x_ref[...]

    big = jax.ShapeDtypeStruct((8192, 8192), jnp.float32)

    def call(x):
        return pl.pallas_call(
            copy_kernel,
            grid=(2,),
            in_specs=[pl.BlockSpec((4096, 8192), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((4096, 8192), lambda i: (i, 0)),
            out_shape=big,
            interpret=True,
        )(x)

    return lambda: jax.eval_shape(call, big)
