"""Plain float32 references, one module per family, found by the name in the
configuration file's ``reference`` key.

:func:`run` trains the configuration's first steps the way the workload
states it, from the same seed as the program, with the exact mean of the
data peers' gradients in place of the program's sync: layer by layer,
each layer's backward recomputing its forward, one data peer per chip at
a time, so that it fits once the program's state is freed.  It imports
nothing of the program.
"""
from __future__ import annotations

import importlib
from contextlib import nullcontext

import jax
import jax.numpy as jnp
import numpy as np

from bench import inputs
from bench.flops import data_peers
from .common import OPTIMIZERS

HEAD_CHUNK = 1024


def load_model(config: dict, precision: str):
    mod = importlib.import_module(f"bench.reference.{config['reference']}")
    return mod.Model(config, precision)


class _Fns:
    """The jitted pieces of one model at one precision."""

    def __init__(self, model):
        self.model = model
        jit = jax.jit

        def one_layer(blocks, l):
            return jax.tree.map(lambda x: jax.lax.dynamic_index_in_dim(x, l, keepdims=False),
                                blocks)

        def layer_fwd(blocks, l, x, rows):
            return model.layer(one_layer(blocks, l), x, rows)

        def layer_bwd(blocks, l, x, rows, g):
            lp = one_layer(blocks, l)
            _, vjp = jax.vjp(lambda p, h: model.layer(p, h, rows), lp, x)
            g_lp, g_x = vjp(g)
            return g_lp, g_x

        def head_fwd_bwd(outer, x, labels, scale):
            loss, vjp = jax.vjp(lambda o, h: model.head(o, h, labels), outer, x)
            g_o, g_x = vjp(scale)
            return loss, g_o, g_x

        def embed_fwd(outer, rows):
            return model.embed(outer, rows)

        def embed_bwd(outer, rows, g):
            _, vjp = jax.vjp(lambda o: model.embed(o, rows), outer)
            return vjp(g)[0]

        def add_layer(acc, l, g_lp):
            return jax.tree.map(
                lambda a, g: jax.lax.dynamic_update_index_in_dim(
                    a, jax.lax.dynamic_index_in_dim(a, l, keepdims=False) + g, l, 0),
                acc, g_lp)

        self.layer_fwd = jit(layer_fwd)
        self.layer_bwd = jit(layer_bwd)
        self.head_fwd_bwd = jit(head_fwd_bwd)
        self.embed_fwd = jit(embed_fwd)
        self.embed_bwd = jit(embed_bwd)
        self.add_layer = jit(add_layer, donate_argnums=(0,))
        self.add = jit(lambda a, b, s: jax.tree.map(lambda x, y: x + s * y, a, b),
                       donate_argnums=(0,))
        self.zeros = jit(lambda t: jax.tree.map(lambda b: jnp.zeros(b.shape, jnp.float32), t))


def _client_grad(fns, params, rows, n_layers: int, count: float):
    """(loss on device, grads) of one data peer's mean token loss over ``rows``;
    ``count`` is the number of labelled positions.  Dispatches without waiting."""
    outer = {k: v for k, v in params.items() if k != "blocks"}
    blocks = params["blocks"]
    labels = rows["labels"]
    xs = [fns.embed_fwd(outer, rows)]
    for l in range(n_layers):
        xs.append(fns.layer_fwd(blocks, jnp.int32(l), xs[-1], rows))
    x = xs.pop()
    s = x.shape[1]
    c = min(HEAD_CHUNK, s)
    loss, g_outer, g_parts = None, None, []
    for i in range(0, s, c):
        li, go, gx = fns.head_fwd_bwd(outer, x[:, i:i + c], labels[:, i:i + c],
                                      jnp.float32(1.0 / count))
        loss = li if loss is None else loss + li
        g_outer = go if g_outer is None else fns.add(g_outer, go, jnp.float32(1.0))
        g_parts.append(gx)
    g = jnp.concatenate(g_parts, axis=1)
    g_blocks = fns.zeros(blocks)
    for l in reversed(range(n_layers)):
        g_lp, g = fns.layer_bwd(blocks, jnp.int32(l), xs[l], rows, g)
        g_blocks = fns.add_layer(g_blocks, jnp.int32(l), g_lp)
    g_outer = fns.add(g_outer, fns.embed_bwd(outer, rows, g), jnp.float32(1.0))
    grads = dict(g_outer)
    grads["blocks"] = g_blocks
    return loss / count, grads


def half_batch_labels(labels):
    """One peer's labels with the second half of its batch left out: its later
    rows, or where it has one row, that row's later positions."""
    rows, seq = labels.shape
    if rows >= 2:
        return labels.at[rows // 2:].set(-1)
    return labels.at[:, seq // 2:].set(-1)


def _rows(batch: dict, lo: int, hi: int) -> dict:
    out = {}
    for k, v in batch.items():
        out[k] = v[:, lo:hi] if k == "positions" else v[lo:hi]
    return out


def run(config: dict, workload: dict, shapes, seed: int, *, steps: int = 3,
        precision: str = "highest", fault: str | None = None, devices=None) -> dict:
    """Readings of the reference's first ``steps`` steps.

    The data peers' gradients are computed one peer per device of
    ``devices`` at a time (all on the default device when None) and summed
    leaf by leaf on the first.  ``fault`` plants one of the faults the
    comparison must catch: ``"half_batch"`` (each peer's loss over the
    first half of its batch, :func:`half_batch_labels`), ``"no_exchange"``
    (every peer steps on peer 0's gradient alone).  Returns ``losses``
    (mean over peers, per step), ``grad_norms`` (per leaf, the first step's
    gradient read back from the optimizer state; also as ``sync_in_norms``)
    and ``update_norms`` (per leaf, |p_steps - p_0|).
    """
    model = load_model(config, precision)
    n_layers = model.layers
    peers = data_peers(workload)
    devices = list(devices) if devices else [None]
    b, seq = int(workload["batch"]), int(workload["seq"])
    opt = workload["optimizer"]
    update, grad_from_state = OPTIMIZERS[opt["name"]](opt)
    put = (lambda x, d: x if d is None else jax.device_put(x, d))
    dev0 = devices[0]
    with jax.default_matmul_precision("highest"), \
            (jax.default_device(dev0) if dev0 is not None else nullcontext()):
        fns = _Fns(model)
        make_params = jax.jit(inputs.param_maker(shapes, config["init"]))
        words = inputs.key_words(seed)
        params = make_params(words)
        leaves0, treedef = jax.tree.flatten(params)
        ndims = [x.ndim for x in leaves0]
        dtypes = [x.dtype for x in leaves0]
        mom = [jnp.zeros(x.shape, x.dtype) for x in leaves0]
        make_batch = jax.jit(inputs.batch_maker(peers * b, seq, **batch_shape(config, workload)))
        upd = jax.jit(lambda p, g, m: [update(pi, gi, mi, nd, dt) for pi, gi, mi, nd, dt
                                       in zip(p, g, m, ndims, dtypes)])
        add_leaf = jax.jit(lambda a, x: a + x, donate_argnums=(0,))
        norms_of = jax.jit(lambda xs: jnp.stack([jnp.sqrt(jnp.sum(jnp.square(
            x.astype(jnp.float32)))) for x in xs]))
        used = [0] if fault == "no_exchange" else list(range(peers))
        losses, grad_norms = [], None
        for step in range(steps):
            batch = make_batch(words, jnp.uint32(step))
            copies = {d: (params if d is dev0 else put(params, d)) for d in devices}
            acc, step_losses = None, []
            for lo in range(0, peers, len(devices)):
                pending = []
                for c in range(lo, min(lo + len(devices), peers)):
                    d = devices[c - lo]
                    rows = _rows(batch, c * b, (c + 1) * b)
                    if fault == "half_batch":
                        rows["labels"] = half_batch_labels(rows["labels"])
                    count = float(np.sum(np.asarray(rows["labels"]) >= 0))
                    loss, g = _client_grad(fns, copies[d], put(rows, d), n_layers, count)
                    pending.append((c, loss, g))
                for c, loss, g in pending:
                    step_losses.append(loss)
                    if c not in used:
                        continue
                    gl = jax.tree.leaves(g)
                    if acc is None:
                        acc = [put(x, dev0) for x in gl]
                    else:
                        acc = [add_leaf(a, put(x, dev0)) for a, x in zip(acc, gl)]
                    del gl
                del pending, g
            del copies
            losses.append(float(np.mean([float(x) for x in step_losses])))
            mean = [x / len(used) for x in acc]
            del acc
            out = upd(jax.tree.leaves(params), mean, mom)
            del mean
            params = jax.tree.unflatten(treedef, [o[0] for o in out])
            mom = [o[1] for o in out]
            if step == 0:
                p0 = jax.tree.leaves(make_params(words))
                grad_norms = np.asarray(norms_of(
                    [grad_from_state(m, p, nd) for m, p, nd in zip(mom, p0, ndims)]))
                del p0
        p0 = jax.tree.leaves(make_params(words))
        update_norms = np.asarray(norms_of(
            [p.astype(jnp.float32) - q.astype(jnp.float32)
             for p, q in zip(jax.tree.leaves(params), p0)]))
    # the reference's sync is the exact mean: what it received is what it gave
    return {"losses": losses, "grad_norms": grad_norms, "sync_in_norms": grad_norms,
            "update_norms": update_norms, "paths": inputs.leaf_paths(shapes)}


def batch_shape(config: dict, workload: dict) -> dict:
    """The batch maker's keyword arguments for a configuration."""
    pub = config["published"]
    vocab = int(config.get("assumed_sizes", {}).get("vocab_size", pub.get("vocab_size", 0)))
    sizes = config.get("assumed_sizes", {})
    return {"vocab": vocab, "patches": int(sizes.get("vlm_patches", 0)),
            "vision_dim": int(sizes.get("vision_dim", 0)),
            "mrope": bool((pub.get("rope_scaling") or {}).get("mrope_section"))}
