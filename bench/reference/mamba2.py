"""Plain float32 Mamba-2 (SSD) language model.

Written from the Mamba-2 paper (arXiv:2405.21060, section 6 and its
"minimal SSD" listing), not from the program:

- blocks: x += Mixer(RMSNorm(x)), no MLP;
- mixer: z = h Wz; x, B, C each through a causal depthwise conv of width
  4 and SiLU; dt = softplus(h Wdt + dt_bias); A = -exp(A_log);
- the selective state space h_t = exp(dt_t A) h_{t-1} + (dt_t x_t) B_t^T,
  y_t = h_t C_t + D x_t, evaluated by the chunked dual form of the
  listing (one group of B and C shared by all heads) with the exact
  segment sums (masked cumulative sums);
- gated norm: RMSNorm(y * silu(z)), then Wo;
- tied embeddings; loss: mean token cross-entropy over labelled positions.

Departures noted: the separate x/B/C convolutions and the five input
matrices are the fused in_proj and conv1d split by output channel (the
same function); RMSNorm multiplies by (1 + scale), the repository's
parameterisation, with eps 1e-6.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .common import dot, rmsnorm


def _segsum(a):
    """(..., T) -> (..., T, T): out[i, j] = a[j+1] + ... + a[i] for i >= j, else -inf."""
    t = a.shape[-1]
    rep = jnp.broadcast_to(a[..., None], a.shape + (t,))           # [..., i, j] = a[i]
    strict = jnp.tril(jnp.ones((t, t), bool), -1)
    rep = jnp.where(strict, rep, 0.0)
    ss = jnp.cumsum(rep, axis=-2)
    return jnp.where(jnp.tril(jnp.ones((t, t), bool)), ss, -jnp.inf)


class Model:
    def __init__(self, config: dict, precision: str):
        pub, red = config["published"], config.get("reduced", {})
        ssm = pub["ssm_cfg"]
        self.d = int(pub["d_model"])
        self.layers = int(red.get("n_layer", pub["n_layer"]))
        self.n = int(ssm["d_state"])
        self.w = int(ssm["d_conv"])
        self.p = int(ssm["headdim"])
        self.inner = int(ssm["expand"]) * self.d
        self.heads = self.inner // self.p
        self.chunk = int(ssm["chunk_size"])
        self.eps = float(config.get("assumed_sizes", {}).get("rms_norm_eps", 1e-6))
        self.precision = precision

    def _mm(self, spec, *xs):
        return dot(self.precision, spec, *xs)

    def embed(self, outer, rows):
        return jnp.take(outer["embed"].astype(jnp.float32), rows["tokens"], axis=0)

    def _conv(self, x, w):
        """Causal depthwise conv: out[t] = sum_i w[i] x[t - (W-1) + i]."""
        s = x.shape[1]
        xp = jnp.pad(x, ((0, 0), (self.w - 1, 0), (0, 0)))
        return sum(xp[:, i:i + s] * w[i] for i in range(self.w))

    def _ssd(self, x, a, bm, cm):
        """x (b,S,H,P) already times dt; a (b,S,H) = dt*A; bm, cm (b,S,N)."""
        b, s, h, p = x.shape
        q = self.chunk
        c = s // q
        xc = x.reshape(b, c, q, h, p)
        ac = a.reshape(b, c, q, h).transpose(0, 3, 1, 2)               # (b,h,c,l)
        bc = bm.reshape(b, c, q, -1)
        cc = cm.reshape(b, c, q, -1)
        a_cum = jnp.cumsum(ac, axis=-1)
        decay = jnp.exp(_segsum(ac))                                    # (b,h,c,l,s)
        cb = self._mm("bcln,bcsn->bcls", cc, bc)
        y_diag = self._mm("bhcls,bcshp->bclhp", cb[:, None] * decay, xc)
        decay_states = jnp.exp(a_cum[..., -1:] - a_cum)                 # (b,h,c,l)
        states = self._mm("bcln,bhcl,bclhp->bchpn", bc, decay_states, xc)
        states = jnp.concatenate([jnp.zeros_like(states[:, :1]), states], axis=1)
        chunk_decay = jnp.exp(_segsum(jnp.pad(a_cum[..., -1], ((0, 0), (0, 0), (1, 0)))))
        new_states = jnp.einsum("bhzc,bchpn->bzhpn", chunk_decay, states,
                                precision=jax.lax.Precision.HIGHEST)
        states = new_states[:, :-1]
        y_off = self._mm("bcln,bchpn,bhcl->bclhp", cc, states, jnp.exp(a_cum))
        return (y_diag + y_off).reshape(b, s, h, p)

    def layer(self, lp, x, rows):
        del rows
        b, s, _ = x.shape
        f32 = lambda t: t.astype(jnp.float32)
        m = lp["mixer"]
        h = rmsnorm(x, f32(lp["ln1"]["scale"]), self.eps)
        z = self._mm("bsd,de->bse", h, m["wz"])
        xs = jax.nn.silu(self._conv(self._mm("bsd,de->bse", h, m["wx"]), f32(m["conv_x"])))
        bs = jax.nn.silu(self._conv(self._mm("bsd,dn->bsn", h, m["wB"]), f32(m["conv_B"])))
        cs = jax.nn.silu(self._conv(self._mm("bsd,dn->bsn", h, m["wC"]), f32(m["conv_C"])))
        dt = jax.nn.softplus(self._mm("bsd,dh->bsh", h, m["wdt"]) + f32(m["dt_bias"]))
        a = -jnp.exp(f32(m["A_log"]))
        xh = xs.reshape(b, s, self.heads, self.p)
        y = self._ssd(xh * dt[..., None], dt * a, bs, cs)
        y = y + xh * f32(m["D"])[:, None]
        y = y.reshape(b, s, self.inner)
        y = rmsnorm(y * jax.nn.silu(z), f32(m["norm"]), self.eps)
        return x + self._mm("bse,ed->bsd", y, m["wo"])

    def head(self, outer, x, labels):
        h = rmsnorm(x, outer["final_norm"]["scale"].astype(jnp.float32), self.eps)
        logits = self._mm("bsd,vd->bsv", h, outer["embed"])
        lse = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, jnp.maximum(labels, 0)[..., None], axis=-1)[..., 0]
        return jnp.sum((lse - ll) * (labels >= 0))
