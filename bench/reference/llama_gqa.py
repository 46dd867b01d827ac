"""Plain float32 reference of a dense GQA decoder (llama / mistral).

Straight ``jax.numpy`` at HIGHEST matmul precision over one whole
sequence: RMSNorm, rotary embedding (rotate-half), grouped-query causal
attention with an optional sliding window, SiLU-gated feed-forward, final
norm, untied head. No cache, no batching, no coding, no kernels, and
nothing imported from the program. It runs layer by layer, so that only
one layer's weights are in float32 at a time.

``forward(..., control=True)`` is the control: the same forward
computed in int8, the precision below the bfloat16 that the
configurations serve in: every matmul takes its weight rounded to int8
per output column and its input rounded to int8 per row (symmetric,
W8A8), and accumulates in float32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench.models.llama_gqa import dims

HI = jax.lax.Precision.HIGHEST
MATRICES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, theta):
    """x: [S, H, hd]; rotate-half with frequencies theta^(-2i/hd)."""
    s, _, hd = x.shape
    half = hd // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _int8(w, axis):
    """Round to int8 along ``axis`` (one scale per slice), symmetric."""
    wf = w.astype(jnp.float32)
    scale = jnp.max(jnp.abs(wf), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.clip(jnp.round(wf / scale), -127, 127) * scale


def _mm(y, w, int8: bool):
    """y @ w in float32; in int8 both go through int8 first (y per row,
    w per output column)."""
    if int8:
        y, w = _int8(y, -1), _int8(w, 0)
    return jnp.dot(y, w.astype(jnp.float32), precision=HI)


@functools.partial(jax.jit, static_argnames=("h", "hkv", "hd", "window",
                                             "theta", "eps", "int8"))
def layer(x, p, *, h, hkv, hd, window, theta, eps, int8=False):
    """One block over the whole sequence. x: [S, d] float32."""
    f32 = lambda a: a.astype(jnp.float32)                  # noqa: E731
    mm = lambda y, w: _mm(y, w, int8)                      # noqa: E731
    s = x.shape[0]
    y = _rms(x, f32(p["ln1"]), eps)
    q = mm(y, p["wq"]).reshape(s, h, hd)
    k = mm(y, p["wk"]).reshape(s, hkv, hd)
    v = mm(y, p["wv"]).reshape(s, hkv, hd)
    q, k = _rope(q, theta), _rope(k, theta)
    g = h // hkv
    k = jnp.repeat(k, g, axis=1)          # query head j reads kv head j//g
    v = jnp.repeat(v, g, axis=1)
    sc = jnp.einsum("qhd,khd->hqk", q, k, precision=HI) * hd ** -0.5
    i = jnp.arange(s)
    mask = i[None, :] <= i[:, None]
    if window:
        mask &= i[None, :] > i[:, None] - window
    sc = jnp.where(mask[None], sc, -jnp.inf)
    a = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, -1), v, precision=HI)
    x = x + mm(a.reshape(s, h * hd), p["wo"])
    y = _rms(x, f32(p["ln2"]), eps)
    ff = jax.nn.silu(mm(y, p["w_gate"])) * mm(y, p["w_up"])
    return x + mm(ff, p["w_down"])


@functools.partial(jax.jit, static_argnames=("eps", "int8"))
def head(x, g, w, *, eps, int8=False):
    return _mm(_rms(x, g.astype(jnp.float32), eps), w, int8)


def forward(weights: dict, hf: dict, tokens, control: bool = False
            ) -> jax.Array:
    """tokens: [S] int -> logits [S, vocab] float32 (``control``: the
    int8 forward)."""
    m = dims(hf)
    kw = dict(h=m["h"], hkv=m["hkv"], hd=m["hd"],
              window=int(hf.get("sliding_window") or 0),
              theta=float(hf["rope_theta"]), eps=float(hf["rms_norm_eps"]),
              int8=control)
    x = weights["embed"][jnp.asarray(tokens)].astype(jnp.float32)
    for i in range(m["L"]):
        p = {k: weights[k][i] for k in MATRICES + ("ln1", "ln2")}
        x = layer(x, p, **kw)
    return head(x, weights["ln_f"], weights["head"], eps=kw["eps"],
                int8=control)

