"""The system under test, driven through its normal training path.

``Program`` builds what ``repro.launch.train`` builds for one workload:
the architecture from ``repro.configs`` with the configuration file's
``arch_changes``, the mesh, ``init_lm``'s parameter tree and logical axes
(its shapes only: the weights come from :mod:`bench.inputs`),
``repro.optim``'s optimizer, a ``TrainStepConfig`` and the jitted step of
``repro.dist.train_step.make_train_step`` with the Pallas codec kernels
on, compiled ahead of time for the workload's shapes and shardings.  This
is the only module of the benchmark that imports the program.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

from bench import inputs
from bench.flops import data_peers
from bench.reference import batch_shape

# the axes of ``repro.launch.mesh.make_mesh_from_spec``, which takes no
# devices: the compile-only check lays the mesh over described chips
MESH_AXES = {1: ("data",), 2: ("data", "model"), 3: ("pod", "data", "model")}

def mesh_dims(workload: dict) -> tuple[int, ...]:
    return tuple(int(x) for x in str(workload["mesh"]).split("x"))


def arch_config(config: dict):
    """The repo's architecture with the configuration file's changes applied."""
    import dataclasses

    from repro.configs import get_config

    cfg = get_config(config["arch"]).replace(**config.get("arch_changes", {}))
    if config.get("ssm_changes"):
        cfg = cfg.replace(ssm=dataclasses.replace(cfg.ssm, **config["ssm_changes"]))
    return cfg


class Program:
    """One workload's compiled step, its shardings and its state."""

    def __init__(self, config: dict, workload: dict, devices, *, use_pallas: bool = True,
                 cfg=None):
        from repro.core.compressors import CompressorConfig
        from repro.dist.train_step import (TrainStepConfig, _opt_specs, batch_pspecs,
                                           ef_bucket_spec, init_ef_state, make_train_step)
        from repro.models import init_lm, transformer
        from repro.optim.optimizers import get_optimizer

        self.config, self.workload = config, workload
        self.cfg = cfg if cfg is not None else arch_config(config)
        dims = mesh_dims(workload)
        self.mesh = jax.make_mesh(dims, MESH_AXES[len(dims)],
                                  axis_types=(AxisType.Auto,) * len(dims),
                                  devices=list(devices)[:math.prod(dims)])
        self.peers = data_peers(workload)
        self.rows = self.peers * int(workload["batch"])
        self.seq = int(workload["seq"])

        holder = {}

        def shapes_of(k):
            p, la = init_lm(k, self.cfg)
            holder["logical"] = la
            return p

        # the key only shapes the trace; no value is used
        self.shapes = jax.eval_shape(shapes_of, jax.random.key(0))
        logical = holder["logical"]

        o = workload["optimizer"]
        kw = {k: v for k, v in o.items() if k != "name"}
        self.opt = get_optimizer(o["name"], **kw)
        method = workload["method"]
        self.ts = TrainStepConfig(
            sync=workload["sync"],
            compressor=CompressorConfig(method=method, bits=int(workload.get("bits", 3)),
                                        use_pallas=use_pallas),
            bucket_mb=float(workload.get("bucket_mb", 4.0)),
            error_feedback=bool(workload.get("ef", False)))

        bshape = batch_shape(config, workload)
        self._check_batch_shape(bshape)
        self.batch_kw = bshape
        sds = jax.ShapeDtypeStruct
        tok = sds((self.rows, self.seq), jnp.int32)
        batch_like = transformer.Batch(
            tokens=tok, labels=tok,
            positions=sds((3, self.rows, self.seq), jnp.int32) if bshape["mrope"] else None,
            patches=(sds((self.rows, bshape["patches"], bshape["vision_dim"]), jnp.float32)
                     if bshape["patches"] else None))
        opt_like = jax.eval_shape(self.opt.init, self.shapes)
        step_fn, pspecs = make_train_step(self.cfg, self.mesh, logical, self.opt, self.ts,
                                          batch_like, opt_state_like=opt_like,
                                          params_like=self.shapes)
        self.step_fn = step_fn
        ns = lambda s: NamedSharding(self.mesh, s)
        is_p = lambda x: isinstance(x, P)
        self.param_sh = jax.tree.map(ns, pspecs, is_leaf=is_p)
        self.opt_sh = jax.tree.map(ns, _opt_specs(opt_like, self.shapes, pspecs), is_leaf=is_p)
        dp = ("pod", "data") if len(dims) == 3 else ("data",)
        self.batch_sh = jax.tree.map(ns, batch_pspecs(batch_like, dp), is_leaf=is_p)
        self.scalar_sh = ns(P())
        self.ef = self.ts.error_feedback
        self.ef_like = None
        if self.ef:
            ef0 = jax.eval_shape(lambda: init_ef_state(self.shapes, self.mesh, pspecs, self.ts))
            self.ef_sh = tuple(ns(ef_bucket_spec(self.mesh)) for _ in ef0)
            self.ef_like = tuple(sds(x.shape, x.dtype, sharding=s)
                                 for x, s in zip(ef0, self.ef_sh))
        with_sh = lambda tree, sh: jax.tree.map(
            lambda x, s: sds(x.shape, x.dtype, sharding=s), tree, sh)
        self.params_like = with_sh(self.shapes, self.param_sh)
        self.opt_like = with_sh(opt_like, self.opt_sh)
        self.batch_like = with_sh(batch_like, self.batch_sh)
        self._batch_type = transformer.Batch
        self._leaf_makers = None
        self.compiled = None

    def _check_batch_shape(self, b: dict) -> None:
        cfg = self.cfg
        want = {"vocab": cfg.vocab, "patches": cfg.vlm_patches,
                "vision_dim": cfg.vlm_vision_dim if cfg.vlm_patches else 0,
                "mrope": cfg.rope == "mrope"}
        if want != b:
            raise ValueError(f"configuration file sizes {b} differ from the program's {want}")

    # -- compile -----------------------------------------------------------

    def state_args_like(self):
        args = [self.params_like, self.opt_like]
        if self.ef:
            args.append(self.ef_like)
        return args

    def lower(self):
        step_like = jax.ShapeDtypeStruct((), jnp.uint32, sharding=self.scalar_sh)
        return self.step_fn.lower(*self.state_args_like(), self.batch_like, step_like)

    def compile(self):
        self.compiled = self.lower().compile()
        return self.compiled

    # -- state and inputs --------------------------------------------------

    def init_state(self, seed: int) -> list:
        params = inputs.make_params(self.shapes, self.config["init"], seed, self.param_sh)
        opt_state = jax.jit(self.opt.init, out_shardings=self.opt_sh)(params)
        state = [params, opt_state]
        if self.ef:
            state.append(jax.jit(
                lambda: tuple(jnp.zeros(x.shape, x.dtype) for x in self.ef_like),
                out_shardings=self.ef_sh)())
        return state

    def batch_fn(self):
        """Jitted ``(seed_words, step) -> Batch`` placed as the step expects."""
        make = inputs.batch_maker(self.rows, self.seq, **self.batch_kw)
        bt = self._batch_type

        def to_batch(words, step):
            d = make(words, step)
            return bt(tokens=d["tokens"], labels=d["labels"], positions=d.get("positions"),
                      patches=d.get("patches"))

        return jax.jit(to_batch, out_shardings=self.batch_sh)

    def step_arg(self, i: int):
        return jax.device_put(np.uint32(i), self.scalar_sh)

    def step(self, state: list, batch, i: int):
        """One step; returns (new state, metrics)."""
        out = self.compiled(*state, batch, self.step_arg(i))
        return list(out[:-1]), out[-1]

    # -- readings for the comparison --------------------------------------

    def _initial_leaves(self, seed: int):
        """The seed's parameters again, one leaf at a time, each materialized in
        its dtype and sharding by a jitted call of its own: one leaf is live
        at a time (so the readings do not raise the memory peak), and XLA
        cannot keep a regenerated leaf at float32 and skip the rounding to
        bfloat16 that the program's parameters went through."""
        if self._leaf_makers is None:
            make = inputs.param_maker(self.shapes, self.config["init"])
            shardings = jax.tree.leaves(self.param_sh)
            self._leaf_makers = [
                jax.jit(lambda w, i=i: jax.tree.leaves(make(w))[i], out_shardings=sh)
                for i, sh in enumerate(shardings)]
        words = inputs.key_words(seed)
        for make_leaf in self._leaf_makers:
            yield make_leaf(words)

    def leaf_norms(self, tree, seed: int, coef_matrices: float, coef_vectors: float):
        """(n_leaves,) norms of tree - coef * p0, p0 the seed's initial parameters."""
        out = []
        for x, p in zip(jax.tree.leaves(tree), self._initial_leaves(seed)):
            c = coef_matrices if p.ndim > 1 else coef_vectors
            out.append(_gap_norm(x, p, jnp.float32(c)))
        return np.asarray(jax.device_get(out))

    def sync_in_norms(self, mom, ef, seed: int, weight_decay: float):
        """Per leaf, the norm of the first step's synced gradient (momentum -
        wd * p0) plus the data peers' mean EF residual after that step, split
        back from the codec's buckets into leaves.  Where the sync quantizes
        each peer's gradient once and keeps all its error (faithful), that
        sum is the mean of the peers' gradients as the sync received them."""
        leaves = jax.tree.leaves(mom)
        sizes = [math.prod(x.shape) for x in leaves]
        if sum(e.shape[1] for e in ef) != sum(sizes):
            raise ValueError("EF buckets do not cover the leaves one to one")
        out, b, off, mean = [], 0, 0, None
        for m, p, n in zip(leaves, self._initial_leaves(seed), sizes):
            while mean is None or off >= mean.shape[0]:
                mean, off = _peer_mean(ef[b]), 0
                b += 1
            c = weight_decay if p.ndim > 1 else 0.0
            out.append(_sync_in_norm(m, p, mean, jnp.float32(c), off, n))
            off += n
        return np.asarray(jax.device_get(out))

    def grad_state(self, opt_state):
        """The optimizer-state tree from which the first gradient is read back."""
        if self.workload["optimizer"]["name"] == "momentum_sgd":
            return opt_state
        raise ValueError(f"no gradient read-back for {self.workload['optimizer']['name']!r}")

    def kernel_calls(self) -> int:
        return self.compiled.as_text().count('custom_call_target="tpu_custom_call"')


@jax.jit
def _gap_norm(x, p, c):
    return jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32) - c * p.astype(jnp.float32))))


@jax.jit
def _peer_mean(e):
    return jnp.mean(e, axis=0)


@partial(jax.jit, static_argnums=(4, 5))
def _sync_in_norm(m, p, mean, c, off, n):
    g = m.astype(jnp.float32) - c * p.astype(jnp.float32)
    g = g + jax.lax.slice(mean, (off,), (off + n,)).reshape(m.shape)
    return jnp.sqrt(jnp.sum(jnp.square(g)))
