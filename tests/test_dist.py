"""Distributed-runtime tests.  Multi-device cases run in subprocesses so the
main pytest process keeps a single CPU device (dry-run contract)."""
import pytest
from multidevice import ROOT, run_with_devices


PRELUDE = """
import jax, jax.numpy as jnp, numpy as np, json
from jax.sharding import NamedSharding, PartitionSpec as P, AxisType
from repro.configs import get_config, reduced
from repro.models import init_lm
from repro.data.synthetic import lm_batch
from repro.optim.optimizers import momentum_sgd
from repro.dist.train_step import make_train_step, TrainStepConfig
from repro.core.compressors import CompressorConfig
"""


def test_streamed_equals_plain_dsgd():
    out = run_with_devices(PRELUDE + """
mesh = jax.make_mesh((4,2), ("data","model"), axis_types=(AxisType.Auto,)*2)
cfg = reduced(get_config("llama3.2-1b")).replace(fsdp=True)
params0, logical = init_lm(jax.random.key(0), cfg)
opt = momentum_sgd(lr=0.05)
res = {}
for name, ts in [("plain", TrainStepConfig(sync="dsgd", streamed=False)),
                 ("stream", TrainStepConfig(sync="dsgd", streamed=True))]:
    batch = lm_batch(cfg, jnp.uint32(0), 8, 128)
    step_fn, pspecs = make_train_step(cfg, mesh, logical, opt, ts, batch)
    sh = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs, is_leaf=lambda x: isinstance(x, P))
    p = jax.device_put(jax.tree.map(jnp.copy, params0), sh)
    o = jax.tree.map(jnp.zeros_like, p)
    losses = []
    for i in range(3):
        p, o, m = step_fn(p, o, lm_batch(cfg, jnp.uint32(i), 8, 128), jnp.uint32(i))
        losses.append(float(m["loss"][0]))
    res[name] = losses
assert np.allclose(res["plain"], res["stream"], atol=1e-4), res
print("OK", json.dumps(res))
""")
    assert "OK" in out


@pytest.mark.parametrize("sync,method", [("faithful", "tnqsgd"), ("two_phase", "tqsgd"), ("two_phase", "tbqsgd")])
def test_compressed_training_converges(sync, method):
    out = run_with_devices(PRELUDE + f"""
mesh = jax.make_mesh((4,2), ("data","model"), axis_types=(AxisType.Auto,)*2)
cfg = reduced(get_config("llama3.2-1b")).replace(fsdp=True)
params0, logical = init_lm(jax.random.key(0), cfg)
opt = momentum_sgd(lr=0.05)
ts = TrainStepConfig(sync="{sync}", compressor=CompressorConfig(method="{method}", bits=4))
batch = lm_batch(cfg, jnp.uint32(0), 8, 128)
step_fn, pspecs = make_train_step(cfg, mesh, logical, opt, ts, batch)
sh = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs, is_leaf=lambda x: isinstance(x, P))
p = jax.device_put(jax.tree.map(jnp.copy, params0), sh)
o = jax.tree.map(jnp.zeros_like, p)
losses = []
for i in range(6):
    p, o, m = step_fn(p, o, lm_batch(cfg, jnp.uint32(i), 8, 128), jnp.uint32(i))
    losses.append(float(m["loss"][0]))
assert losses[-1] < losses[0] - 0.2, losses
print("OK", losses)
""")
    assert "OK" in out


def test_multipod_mesh_compressed():
    out = run_with_devices(PRELUDE + """
mesh = jax.make_mesh((2,2,2), ("pod","data","model"), axis_types=(AxisType.Auto,)*3)
cfg = reduced(get_config("llama3.2-1b")).replace(fsdp=True)
params0, logical = init_lm(jax.random.key(0), cfg)
opt = momentum_sgd(lr=0.05)
for sync in ("dsgd", "two_phase", "hierarchical", "faithful"):
    ts = TrainStepConfig(sync=sync, compressor=CompressorConfig(method="tnqsgd", bits=4))
    batch = lm_batch(cfg, jnp.uint32(0), 8, 128)
    step_fn, pspecs = make_train_step(cfg, mesh, logical, opt, ts, batch)
    sh = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs, is_leaf=lambda x: isinstance(x, P))
    p = jax.device_put(jax.tree.map(jnp.copy, params0), sh)
    o = jax.tree.map(jnp.zeros_like, p)
    losses = []
    for i in range(3):
        p, o, m = step_fn(p, o, lm_batch(cfg, jnp.uint32(i), 8, 128), jnp.uint32(i))
        losses.append(float(m["loss"][0]))
    assert losses[-1] < losses[0], (sync, losses)
    print(sync, "OK", losses)
""")
    assert out.count("OK") == 4


# The former single-mesh codec spot check (test_sharded_codec_units) is
# superseded by tests/mesh_sweep.py: a parameterized mesh-shape ×
# sync-method sweep pinning bitwise peer agreement and equality with the
# single-device reference codec (repro.dist.reference).


def test_bucketed_matches_per_leaf_mean():
    """The bucketed codec must agree with the per-leaf codec up to
    quantization noise while issuing a mode-bounded number of collectives
    (1 all-gather for faithful, all-to-all + all-gather for two_phase) —
    independent of the leaf count.

    Reuses the exact demo script from ``benchmarks/collectives_bench.py``
    (which asserts these properties itself), so bench and test measure the
    same thing by construction."""
    import sys

    sys.path.insert(0, str(ROOT))
    try:
        from benchmarks.collectives_bench import _bucketed_demo_rows
    finally:
        sys.path.pop(0)

    rows = {r.split(",")[1]: r.split(",")[3] for r in _bucketed_demo_rows()}
    assert rows.get("bucketed_demo") == "OK", rows
    assert int(rows["two_phase_bucket_n_collectives"]) == 2, rows
    assert int(rows["faithful_bucket_n_collectives"]) == 1, rows
    assert int(rows["two_phase_leaf_n_collectives"]) >= int(rows["n_grad_leaves"]), rows
    assert int(rows["faithful_leaf_n_collectives"]) >= int(rows["n_grad_leaves"]), rows


def test_opt_specs_with_non_mirror_leaves():
    """A scalar step counter in the optimizer state must not knock the
    mirrored momentum leaves back to full replication."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.dist.train_step import _opt_specs

    params = {"w": jnp.zeros((8, 4)), "b": jnp.zeros((4,))}
    pspecs = {"w": P("model", None), "b": P(None)}

    # mirror + scalar counter: leaf count not divisible by param count
    opt_state = {"count": jnp.zeros(()), "mu": {"w": jnp.zeros((8, 4)), "b": jnp.zeros((4,))}}
    specs = _opt_specs(opt_state, params, pspecs)
    flat = dict(zip(["count", "mu.b", "mu.w"], jax.tree.leaves(specs, is_leaf=lambda s: isinstance(s, P))))
    assert flat["count"] == P()
    # dict flattening is key-sorted: b before w
    assert flat["mu.b"] == P(None)
    assert flat["mu.w"] == P("model", None)

    # two mirrors (AdamW-style) still cycle through the param specs
    opt2 = {"m": dict(params), "v": dict(params)}
    specs2 = _opt_specs(opt2, params, pspecs)
    leaves2 = jax.tree.leaves(specs2, is_leaf=lambda s: isinstance(s, P))
    assert leaves2 == [P(None), P("model", None), P(None), P("model", None)]


def test_pack_dim_roundtrip():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.dist.sharded_codec import pack_dim, unpack_dim

    codes = jax.random.randint(jax.random.key(0), (3, 128, 5), 0, 8).astype(jnp.uint8)
    w = pack_dim(codes, 1, 3)
    assert w.shape == (3, 12, 5)
    back = unpack_dim(w, 1, 3)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(codes))


def test_serve_fns_multidevice():
    out = run_with_devices(PRELUDE + """
from repro.dist.serve_step import make_serve_fns
from repro.models.transformer import Batch
mesh = jax.make_mesh((4,2), ("data","model"), axis_types=(AxisType.Auto,)*2)
cfg = reduced(get_config("llama3.2-1b"))
params, logical = init_lm(jax.random.key(0), cfg)
batch = lm_batch(cfg, jnp.uint32(0), 8, 64)
prefill_fn, decode_fn, pspecs, cspecs = make_serve_fns(cfg, mesh, logical, batch, 8, 64, params_like=params)
sh = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs, is_leaf=lambda x: isinstance(x, P))
p = jax.device_put(params, sh)
logits, caches = prefill_fn(p, batch)
assert logits.shape == (8, cfg.vocab)
tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
logits2, caches2 = decode_fn(p, tok, caches, jnp.int32(64))
assert bool(jnp.all(jnp.isfinite(logits2)))
print("OK")
""")
    assert "OK" in out
