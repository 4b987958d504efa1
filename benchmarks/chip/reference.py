"""Plain reference of the benchmark's models: forward, loss and gradient.

Straightforward ``jax.numpy`` over the parameter tree the system trains
(``embed``, ``groups`` stacked along a leading group axis, ``final_norm``,
``lm_head`` when untied), written from the layer equations and reading
its sizes from the configuration file's ``shape`` block.  It imports
nothing of the program.  Every matrix product runs at ``precision``
(``HIGHEST`` for the reference, so a float32 product is a float32
product on a TPU too) and every activation in ``dtype``.  The control
of ``correct`` is this same code one precision down: ``dtype=bfloat16``
for a float32 configuration, and ``operand_dtype=float8_e4m3fn`` for a
bfloat16 one: the operands of every product rounded to fp8 on the way
in, activations and cotangents bfloat16 (the rounding passes gradients
straight through, so the backward pass is not rounded to fp8 too).

Departures from the formulations the program uses, none of which
changes the result in exact arithmetic:

* mLSTM is the quadratic parallel form over the whole sequence with a
  row-max stabiliser (xLSTM paper, appendix), not the chunkwise scan;
  its output ``h / max(|n|, exp(-m))`` is independent of the stabiliser.
* attention is one dense causal (and windowed) softmax, not blocks.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


class Ref:
    """One configuration's reference numerics."""

    def __init__(self, shape: Dict[str, Any], dtype=jnp.float32,
                 operand_dtype=None, precision=HIGHEST):
        self.s = shape
        self.dtype = jnp.dtype(dtype)
        self.operand_dtype = operand_dtype
        self.precision = precision

    # -------------------------------------------------------- primitives
    @functools.cached_property
    def _round(self):
        """Round to ``operand_dtype`` and back; the gradient passes
        through unrounded."""
        od = self.operand_dtype

        @jax.custom_vjp
        def r(x):
            return x.astype(od).astype(x.dtype)
        r.defvjp(lambda x: (r(x), None), lambda _, g: (g,))
        return r

    def mm(self, eq, a, b):
        if self.operand_dtype is not None:
            a, b = self._round(a), self._round(b)
        out = jnp.einsum(eq, a, b, precision=self.precision,
                         preferred_element_type=jnp.float32)
        return out.astype(self.dtype)

    def rmsnorm(self, x, scale, eps):
        var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
        return x * jax.lax.rsqrt(var + eps) * scale.astype(self.dtype)

    @staticmethod
    def silu(x):
        return x * jax.nn.sigmoid(x)

    @staticmethod
    def gelu_tanh(x):
        c = math.sqrt(2.0 / math.pi)
        return 0.5 * x * (1.0 + jnp.tanh(c * (x + 0.044715 * x ** 3)))

    # ------------------------------------------------------------ mixers
    def attention(self, p, x):
        s = self.s
        B, S, _ = x.shape
        H, KV = s["num_heads"], s["num_kv_heads"]
        hd = s["head_dim"]
        q = self.mm("bsd,dhk->bshk", x, p["wq"])
        k = self.mm("bsd,dhk->bshk", x, p["wk"])
        v = self.mm("bsd,dhk->bshk", x, p["wv"])
        half = hd // 2
        inv = 1.0 / (s["rope_theta"] ** (jnp.arange(half, dtype=jnp.float32)
                                         / half))
        ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv
        cos = jnp.cos(ang)[None, :, None, :].astype(self.dtype)
        sin = jnp.sin(ang)[None, :, None, :].astype(self.dtype)

        def rope(t):
            t1, t2 = t[..., :half], t[..., half:]
            return jnp.concatenate([t1 * cos - t2 * sin,
                                    t1 * sin + t2 * cos], axis=-1)

        q, k = rope(q), rope(k)
        # grouped-query attention: query head h reads kv head h // (H/KV)
        k = jnp.repeat(k, H // KV, axis=2)
        v = jnp.repeat(v, H // KV, axis=2)
        scores = self.mm("bqhd,bshd->bhqs", q, k) * hd ** -0.5
        pos = jnp.arange(S)
        allowed = pos[None, :] <= pos[:, None]
        if s.get("sliding_window"):
            allowed &= pos[None, :] > pos[:, None] - s["sliding_window"]
        scores = jnp.where(allowed[None, None], scores, -jnp.inf)
        w = jax.nn.softmax(scores, axis=-1)
        out = self.mm("bhqs,bshd->bqhd", w, v)
        return self.mm("bshk,hkd->bsd", out, p["wo"])

    def mlstm(self, p, x):
        s = self.s
        B, S, D = x.shape
        H = s["num_heads"]
        di = int(s["xlstm_proj_factor"] * D)
        dh = di // H
        dc = s["xlstm_conv"]
        u = self.mm("bsd,de->bse", x, p["w_up"])
        z = self.mm("bsd,de->bse", x, p["w_z"])
        # causal depthwise convolution: tap dc-1 multiplies the current
        # step, tap 0 the step dc-1 back
        up = jnp.pad(u, ((0, 0), (dc - 1, 0), (0, 0)))
        xc = sum(up[:, i:i + S] * p["conv_w"][i].astype(self.dtype)
                 for i in range(dc))
        xc = self.silu(xc + p["conv_b"].astype(self.dtype))
        q = self.mm("bse,ehk->bhsk", xc, p["lq"]) * dh ** -0.5
        k = self.mm("bse,ehk->bhsk", xc, p["lk"])
        v = self.mm("bse,ehk->bhsk", u, p["lv"])
        gates = self.mm("bse,ehg->bhsg", xc, p["w_if"]) \
            + p["b_if"][None, :, None, :].astype(self.dtype)
        li = gates[..., 0]                                  # (B,H,S)
        lf = jax.nn.log_sigmoid(gates[..., 1])
        F = jnp.cumsum(lf, axis=-1)
        logd = F[..., :, None] - F[..., None, :] + li[..., None, :]
        causal = jnp.tril(jnp.ones((S, S), bool))
        logd = jnp.where(causal, logd, -jnp.inf)
        m = jnp.max(logd, axis=-1, keepdims=True)           # (B,H,S,1)
        d = jnp.exp(logd - m)
        c = self.mm("bhtd,bhsd->bhts", q, k) * d
        n = jnp.sum(c, axis=-1, keepdims=True)
        h = self.mm("bhts,bhsd->bhtd", c, v) \
            / jnp.maximum(jnp.abs(n), jnp.exp(-m))
        # per-head RMS norm over the head's channels
        var = jnp.mean(jnp.square(h), axis=-1, keepdims=True)
        h = h * jax.lax.rsqrt(var + 1e-5) \
            * p["gn_scale"].astype(self.dtype)[None, :, None, :]
        h = jnp.transpose(h, (0, 2, 1, 3)).reshape(B, S, di)
        return self.mm("bse,ed->bsd", h * self.silu(z), p["w_down"])

    def slstm(self, p, x):
        s = self.s
        B, S, D = x.shape
        H = s["num_heads"]
        dh = D // H
        xg = self.mm("bsd,dge->bsge", x, p["w_x"]) \
            + p["b"].astype(self.dtype)
        r = p["r_h"]

        def step(state, xt):
            c0, n0, m0, h0 = state
            rec = self.mm("bhd,hdge->bghe", h0.reshape(B, H, dh), r)
            g = xt + rec.reshape(B, 4, D)
            zt = jnp.tanh(g[:, 0])
            it = g[:, 1]
            ft = jax.nn.log_sigmoid(g[:, 2])
            ot = jax.nn.sigmoid(g[:, 3])
            m1 = jnp.maximum(jnp.maximum(ft + m0, it), -30.0)
            fd = jnp.exp(ft + m0 - m1)
            ie = jnp.exp(it - m1)
            c1 = fd * c0 + ie * zt
            n1 = fd * n0 + ie
            h1 = ot * c1 / jnp.maximum(n1, 1e-6)
            return (c1, n1, m1, h1), h1

        zeros = jnp.zeros((B, D), self.dtype)
        init = (zeros, zeros, jnp.full((B, D), -30.0, self.dtype), zeros)
        _, hs = jax.lax.scan(step, init, jnp.swapaxes(xg, 0, 1))
        h = self.rmsnorm(jnp.swapaxes(hs, 0, 1), p["gn_scale"], 1e-5)
        up = self.mm("bsd,de->bse", h, p["w_up"])
        return self.mm("bse,ed->bsd", self.gelu_tanh(up), p["w_down"])

    def mlp(self, p, x):
        gate = self.mm("bsd,df->bsf", x, p["w_gate"])
        up = self.mm("bsd,df->bsf", x, p["w_up"])
        return self.mm("bsf,fd->bsd", self.silu(gate) * up, p["w_down"])

    # -------------------------------------------------------------- model
    def loss(self, params, tokens, labels):
        """Mean next-token cross-entropy over every position."""
        s = self.s
        eps = s["norm_eps"]
        x = jnp.take(params["embed"], tokens, axis=0).astype(self.dtype)
        mixers = {"attn": self.attention, "mlstm": self.mlstm,
                  "slstm": self.slstm}
        for g in range(s["num_groups"]):
            for j, (mixer, ffn) in enumerate(s["block_pattern"]):
                lp = jax.tree.map(lambda t: t[g], params["groups"][j])
                h = self.rmsnorm(x, lp["mixer_norm"]["scale"], eps)
                x = x + mixers[mixer](lp["mixer"], h)
                if ffn == "mlp":
                    h = self.rmsnorm(x, lp["ffn_norm"]["scale"], eps)
                    x = x + self.mlp(lp["ffn"], h)
        x = self.rmsnorm(x, params["final_norm"]["scale"], eps)
        head = params["embed"].T if s["tie_embeddings"] \
            else params["lm_head"]
        logits = self.mm("bsd,dv->bsv", x, head).astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
        return jnp.mean(logz - gold)

    @functools.cached_property
    def grad(self):
        """jitted ``(params, tokens, labels) -> (loss, gradient tree)``,
        both float32; the parameters enter in ``dtype``."""
        def g(params, tokens, labels):
            p = jax.tree.map(lambda t: t.astype(self.dtype), params)
            loss, grads = jax.value_and_grad(self.loss)(p, tokens, labels)
            return loss, jax.tree.map(lambda t: t.astype(jnp.float32),
                                      grads)
        return jax.jit(g)
