"""Plain float32 decoder-only transformer with GQA, SwiGLU and (M-)RoPE.

Written from the published Qwen2 / Qwen2-VL description (arXiv:2407.10671,
arXiv:2409.12191), not from the program:

- pre-norm blocks: x += Attn(RMSNorm(x)); x += MLP(RMSNorm(x));
- grouped-query attention, query head ``h`` reading key/value head
  ``h // (heads / kv_heads)``, causal softmax(q k^T / sqrt(head_dim)) v;
- rotary embeddings in the rotate-half form, theta from the config; M-RoPE
  splits the head_dim/2 frequencies into (t, h, w) sections (16, 24, 24)
  and rotates each section by its own position stream;
- SwiGLU MLP: (silu(x Wi) * (x Wg)) Wo;
- tied embeddings; the vision stub replaces the first P positions with
  ``patches @ projector``;
- loss: mean token cross-entropy over labelled positions.

Departure noted: RMSNorm multiplies by (1 + scale), the repository's
parameterisation of the same norm (the published weight w is 1 + scale).
Parameters arrive in the program's leaf layout (``blocks`` stacked by
layer), as :mod:`bench.inputs` makes them.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .common import dot, rmsnorm


class Model:
    def __init__(self, config: dict, precision: str):
        pub, red = config["published"], config.get("reduced", {})
        self.d = int(pub["hidden_size"])
        self.heads = int(pub["num_attention_heads"])
        self.kv = int(pub["num_key_value_heads"])
        self.hd = self.d // self.heads
        self.theta = float(pub["rope_theta"])
        self.layers = int(red.get("num_hidden_layers", pub["num_hidden_layers"]))
        rs = pub.get("rope_scaling") or {}
        self.sections = tuple(rs.get("mrope_section", ()))
        self.eps = float(config.get("assumed_sizes", {}).get("rms_norm_eps", 1e-6))
        self.precision = precision

    def _mm(self, spec, *xs):
        return dot(self.precision, spec, *xs)

    def embed(self, outer, rows):
        x = jnp.take(outer["embed"].astype(jnp.float32), rows["tokens"], axis=0)
        if "patches" in rows:
            proj = self._mm("bpv,vd->bpd", rows["patches"], outer["projector"])
            x = jnp.concatenate([proj, x[:, proj.shape[1]:]], axis=1)
        return x

    def _rotate(self, x, positions):
        """x: (B, S, H, hd); positions: (3, B, S) for M-RoPE or (B, S)."""
        half = self.hd // 2
        inv = self.theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
        if self.sections:
            which = jnp.concatenate([jnp.full((n,), i) for i, n in enumerate(self.sections)])
            pos = positions.astype(jnp.float32)[which]            # (half, B, S)
            ang = jnp.moveaxis(pos, 0, -1) * inv                    # (B, S, half)
        else:
            ang = positions.astype(jnp.float32)[..., None] * inv
        cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
        x1, x2 = x[..., :half], x[..., half:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)

    def layer(self, lp, x, rows):
        b, s, _ = x.shape
        f32 = lambda t: t.astype(jnp.float32)
        h = rmsnorm(x, f32(lp["ln1"]["scale"]), self.eps)
        a = lp["mixer"]
        q = self._mm("bsd,de->bse", h, a["wq"]).reshape(b, s, self.heads, self.hd)
        k = self._mm("bsd,de->bse", h, a["wk"]).reshape(b, s, self.kv, self.hd)
        v = self._mm("bsd,de->bse", h, a["wv"]).reshape(b, s, self.kv, self.hd)
        pos = rows["positions"] if "positions" in rows else \
            jnp.broadcast_to(jnp.arange(s)[None], (b, s))
        q, k = self._rotate(q, pos), self._rotate(k, pos)
        rep = self.heads // self.kv
        qg = q.reshape(b, s, self.kv, rep, self.hd)
        sc = self._mm("bqgrd,bkgd->bgrqk", qg, k) / jnp.sqrt(jnp.float32(self.hd))
        causal = jnp.tril(jnp.ones((s, s), bool))
        sc = jnp.where(causal, sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        o = self._mm("bgrqk,bkgd->bqgrd", p, v).reshape(b, s, self.heads * self.hd)
        x = x + self._mm("bse,ed->bsd", o, a["wo"])
        h2 = rmsnorm(x, f32(lp["ln2"]["scale"]), self.eps)
        f = lp["ffn"]
        u = jax.nn.silu(self._mm("bsd,df->bsf", h2, f["wi"])) * self._mm("bsd,df->bsf", h2, f["wg"])
        return x + self._mm("bsf,fd->bsd", u, f["wo"])

    def head(self, outer, x, labels):
        """Sum over labelled positions of the token cross-entropy."""
        h = rmsnorm(x, outer["final_norm"]["scale"].astype(jnp.float32), self.eps)
        logits = self._mm("bsd,vd->bsv", h, outer["embed"])
        lse = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, jnp.maximum(labels, 0)[..., None], axis=-1)[..., 0]
        return jnp.sum((lse - ll) * (labels >= 0))
