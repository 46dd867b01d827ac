"""FFN blocks: dense (SwiGLU / GELU) and Mixture-of-Experts.

Dense: W1/W3 are column-parallel => CODED in coded mode; W2 row-parallel,
never coded (paper Table 1).

MoE: routed experts are sharded over the `model` axis (expert parallelism);
CDC is NOT applied across experts — routing is input-dependent, so no shared
factor exists between expert outputs (the same algebra that rules out input
splitting in paper Eq. 13-14; DESIGN.md §3). Shared experts are an ordinary
dense FFN and ARE coded. Dispatch is sort-based with a capacity bound
(MaxText-style "dropping"), which lowers to sort+scatter HLO and shards to
all-to-all-ish collectives under EP — no [tokens, E, capacity] one-hot blowup.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.common import (Params, TPCtx, activation, col_dense,
                                 linear_init, row_dense)


def ffn_init(key, cfg, ctx: TPCtx, dtype, d_ff: int | None = None) -> Params:
    d = cfg.d_model
    f = d_ff if d_ff is not None else cfg.d_ff
    ks = jax.random.split(key, 3)
    p = {
        "w1": linear_init(ks[0], d, f, ctx, dtype),
        "w2": linear_init(ks[1], f, d, ctx, dtype,
                          scale=1.0 / f ** 0.5, coded=False),
    }
    if cfg.act == "silu":  # gated
        p["w3"] = linear_init(ks[2], d, f, ctx, dtype)
    return p


def ffn(ctx: TPCtx, p: Params, cfg, x: jax.Array, valid=None,
        d_ff: int | None = None) -> jax.Array:
    f = d_ff if d_ff is not None else cfg.d_ff
    h = col_dense(ctx, p["w1"], x, f, valid)
    h = activation(cfg.act, h)
    if "w3" in p:
        h = h * col_dense(ctx, p["w3"], x, f, valid)
    return row_dense(ctx, p["w2"], h)


# ------------------------------------------------------------------ MoE ----

def _pad_experts(n_experts: int, tp: int) -> int:
    """EP requires n_experts % tp == 0 (qwen2's 60 -> 64; extra experts are
    real parameters but the router never selects them beyond noise)."""
    return ((n_experts + tp - 1) // tp) * tp


def moe_init(key, cfg, ctx: TPCtx, dtype) -> Params:
    d, fe = cfg.d_model, cfg.d_ff_expert
    e = _pad_experts(cfg.n_experts, ctx.tp)
    ks = jax.random.split(key, 5)
    scale = 1.0 / d ** 0.5
    p: Params = {
        "router": {"w": (jax.random.normal(ks[0], (d, e), jnp.float32)
                         * scale).astype(dtype)},
        # experts stacked on a leading E axis (sharded over `model` = EP)
        "we1": (jax.random.normal(ks[1], (e, d, fe), jnp.float32)
                * scale).astype(dtype),
        "we3": (jax.random.normal(ks[2], (e, d, fe), jnp.float32)
                * scale).astype(dtype),
        "we2": (jax.random.normal(ks[3], (e, fe, d), jnp.float32)
                * (1.0 / fe ** 0.5)).astype(dtype),
    }
    if cfg.n_shared_experts:
        p["shared"] = ffn_init(ks[4], cfg, ctx, dtype,
                               d_ff=cfg.n_shared_experts * fe)
    return p


def _route(ctx: TPCtx, router_w, xf, k: int, e: int):
    """Shared routing math: top-k gates + globally-sorted dispatch order.

    Deterministic and identical on every rank (inputs are model-replicated),
    so the sharded path needs NO routing communication at all.
    """
    n = xf.shape[0]
    logits = (xf @ router_w).astype(jnp.float32)  # [N, E]
    probs = jax.nn.softmax(logits, axis=-1)
    gates, eidx = jax.lax.top_k(probs, k)  # [N, k]
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    m = n * k
    flat_e = eidx.reshape(m)
    flat_g = gates.reshape(m)
    flat_t = jnp.repeat(jnp.arange(n), k)
    order = jnp.argsort(flat_e)
    se, sg, st = flat_e[order], flat_g[order], flat_t[order]
    grp_start = jnp.searchsorted(se, se, side="left")
    pos = jnp.arange(m) - grp_start
    if ctx.moe_capacity and ctx.moe_capacity > 0:
        cap = int(max(1, ctx.moe_capacity * m / e))
    else:
        cap = m  # no dropping (exactness mode; memory O(E*M))
    keep = pos < cap
    return se, sg, st, pos, keep, cap


def _expert_ffn(buf, we1, we3, we2):
    h = jnp.einsum("ecd,edf->ecf", buf, we1)
    h = activation("silu", h)
    h = h * jnp.einsum("ecd,edf->ecf", buf, we3)
    return jnp.einsum("ecf,efd->ecd", h, we2)  # [E, cap, D]


def moe(ctx: TPCtx, p: Params, cfg, x: jax.Array, valid=None) -> jax.Array:
    """Top-k routed MoE with sort-based capacity dispatch.

    x: [B, S, D] -> [B, S, D].

    Sharded path (§Perf hillclimb 2): the naive GSPMD lowering of the
    scatter-add dispatch moved ~150 TB/step of all-reduce on qwen3-moe
    train_4k (the [E, cap, D] buffers and [N, D] combine cross the token <->
    expert sharding boundary per layer). Because activations are REPLICATED
    over `model`, each rank can dispatch tokens to its OWN expert slab with
    zero communication; the only wire cost is one bf16 psum of [N, D] for
    the combine — the same bytes as a megatron FFN all-reduce.
    """
    b, s, d = x.shape
    k = cfg.top_k
    e = p["we1"].shape[0]
    n = b * s
    tp = (ctx.mesh.shape[ctx.axis]
          if ctx.mesh is not None and ctx.axis in ctx.mesh.axis_names else 1)

    if tp > 1 and e % tp == 0:
        y = _moe_sharded(ctx, p, cfg, x.reshape(n, d), e, k, tp)
    else:
        y = _moe_local(ctx, p, x.reshape(n, d), e, k)
    y = y.reshape(b, s, d)

    if "shared" in p:
        y = y + ffn(ctx, p["shared"], cfg, x, valid,
                    d_ff=cfg.n_shared_experts * cfg.d_ff_expert)
    return y


def _moe_local(ctx: TPCtx, p: Params, xf, e: int, k: int):
    se, sg, st, pos, keep, cap = _route(ctx, p["router"]["w"], xf, k, e)
    d = xf.shape[-1]
    buf = jnp.zeros((e, cap, d), xf.dtype)
    buf = buf.at[se, jnp.minimum(pos, cap - 1)].add(
        jnp.where(keep[:, None], xf[st], 0))
    out = _expert_ffn(buf, p["we1"], p["we3"], p["we2"])
    y = jnp.zeros((xf.shape[0], d), jnp.float32)
    contrib = out[se, jnp.minimum(pos, cap - 1)].astype(jnp.float32)
    y = y.at[st].add(jnp.where(keep[:, None], contrib * sg[:, None], 0))
    return y.astype(xf.dtype)


def _moe_sharded(ctx: TPCtx, p: Params, cfg, xf, e: int, k: int, tp: int):
    """Full-manual shard_map: tokens stay on their batch shard, experts on
    their EP rank; routing math is local (N_local tokens), dispatch is
    local, the combine is ONE psum over the EP axis."""
    from jax.sharding import PartitionSpec as P

    e_local = e // tp
    axis = ctx.axis
    mesh = ctx.mesh
    batch_axes = tuple(a for a in ("pod", ctx.fsdp)
                       if a and a in mesh.axis_names)
    n = xf.shape[0]
    n_batch = 1
    for a in batch_axes:
        n_batch *= mesh.shape[a]
    if n % n_batch or not batch_axes:
        batch_axes = ()  # tiny batches: replicate tokens over batch axes

    def f(xf, router_w, we1, we3, we2):
        rank = jax.lax.axis_index(axis)
        se, sg, st, pos, keep, cap = _route(ctx, router_w, xf, k, e)
        d = xf.shape[-1]
        e0 = rank * e_local
        mine = (se >= e0) & (se < e0 + e_local) & keep
        se_l = jnp.clip(se - e0, 0, e_local - 1)
        # local dispatch: tokens already resident, experts already resident
        buf = jnp.zeros((e_local, cap, d), xf.dtype)
        buf = buf.at[se_l, jnp.minimum(pos, cap - 1)].add(
            jnp.where(mine[:, None], xf[st], 0))
        out = _expert_ffn(buf, we1, we3, we2)
        contrib = out[se_l, jnp.minimum(pos, cap - 1)]
        y = jnp.zeros((xf.shape[0], d), xf.dtype)
        y = y.at[st].add(
            jnp.where(mine[:, None],
                      contrib * sg[:, None].astype(contrib.dtype), 0))
        # ONE combine: psum over the EP axis (the only wire cost)
        return jax.lax.psum(y, axis)

    x_spec = P(batch_axes if batch_axes else None, None)
    fn = jax.shard_map(
        f, mesh=mesh,
        in_specs=(x_spec, P(None, None), P(axis, None, None),
                  P(axis, None, None), P(axis, None, None)),
        out_specs=x_spec, check_vma=False)
    return fn(xf, p["router"]["w"], p["we1"], p["we3"], p["we2"])


def moe_aux_loss(p: Params, cfg, x: jax.Array) -> jax.Array:
    """Load-balance auxiliary loss (Switch-style): E * sum_e f_e * p_e."""
    b, s, d = x.shape
    logits = (x.reshape(-1, d) @ p["router"]["w"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, -1)
    e = probs.shape[-1]
    _, eidx = jax.lax.top_k(probs, cfg.top_k)
    frac = jnp.mean(jax.nn.one_hot(eidx, e, dtype=jnp.float32), axis=(0, 1))
    imp = probs.mean(0)
    return e * jnp.sum(frac * imp)
