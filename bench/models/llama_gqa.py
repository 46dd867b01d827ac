"""Dense GQA decoder (llama / mistral family): the benchmark's weights.

``make_weights`` draws every tensor of the model from the seed, in the
type it is served in, in one jitted call on the device. The program gets
them through ``to_program`` (its parameter layout; parity is left for
the program to encode), and the plain reference draws the same tensors
again from the same seed. Nothing here imports the program.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp


def dims(hf: dict) -> dict:
    d = hf["hidden_size"]
    h = hf["num_attention_heads"]
    return {"d": d, "h": h, "hkv": hf["num_key_value_heads"],
            "hd": hf.get("head_dim") or d // h,
            "f": hf["intermediate_size"], "v": hf["vocab_size"],
            "L": hf["num_hidden_layers"]}


def shapes(hf: dict) -> dict[str, tuple[tuple[int, ...], float]]:
    """name -> (shape, init std). Layer tensors are stacked on a leading
    layer axis; matrices are [in, out]."""
    m = dims(hf)
    d, h, hkv, hd, f, v, L = (m[k] for k in ("d", "h", "hkv", "hd", "f",
                                              "v", "L"))
    return {
        "embed": ((v, d), 1.0),
        "wq": ((L, d, h * hd), d ** -0.5),
        "wk": ((L, d, hkv * hd), d ** -0.5),
        "wv": ((L, d, hkv * hd), d ** -0.5),
        "wo": ((L, h * hd, d), (h * hd) ** -0.5),
        "w_gate": ((L, d, f), d ** -0.5),
        "w_up": ((L, d, f), d ** -0.5),
        "w_down": ((L, f, d), f ** -0.5),
        "ln1": ((L, d), 0.1),
        "ln2": ((L, d), 0.1),
        "ln_f": ((d,), 0.1),
        "head": ((d, v), d ** -0.5),
    }


NORMS = ("ln1", "ln2", "ln_f")


def root_key(seed: int) -> jax.Array:
    """A key from any non-negative seed, also one past 32 bits."""
    seed = int(seed) % 2 ** 64
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


def _draw(key, name: str, shape, std: float, dtype):
    k = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
    x = jax.random.normal(k, shape, jnp.float32) * std
    if name in NORMS:                     # gains near 1, kept in float32
        return 1.0 + x
    return x.astype(dtype)


def make_weights(hf: dict, key: jax.Array, dtype=jnp.bfloat16) -> dict:
    """Every tensor, drawn from ``key`` (call under ``jax.jit``)."""
    return {name: _draw(key, name, shape, std, dtype)
            for name, (shape, std) in shapes(hf).items()}


def _pad_cols(w, m: int):
    pad = m - w.shape[-1]
    if pad == 0:
        return w
    return jnp.pad(w, [(0, 0)] * (w.ndim - 1) + [(0, pad)])


def to_program(w: dict, hf: dict, tp: int) -> dict:
    """The program's parameter tree for ``repro.models`` (coded mode):
    coded column GEMMs carry a ``cdc`` placeholder that the program's
    offline encode fills; padded columns and rows are zero."""
    q = tp * tp
    pad = lambda m: -(-m // q) * q          # noqa: E731  (TPCtx.pad_dim)
    v = w["embed"].shape[0]
    coded = lambda x: {"w": _pad_cols(x, pad(x.shape[-1])),   # noqa: E731
                       "cdc": None}
    embed = jnp.pad(w["embed"], ((0, pad(v) - v), (0, 0)))
    return {
        "embed": embed,
        "ln_f": {"g": w["ln_f"]},
        "lm_head": coded(w["head"]),
        "layers": {
            "ln1": {"g": w["ln1"]},
            "ln2": {"g": w["ln2"]},
            "attn": {"wq": coded(w["wq"]), "wk": coded(w["wk"]),
                     "wv": coded(w["wv"]), "wo": {"w": w["wo"]}},
            "ffn": {"w1": coded(w["w_gate"]), "w3": coded(w["w_up"]),
                    "w2": {"w": w["w_down"]}},
        },
    }


def arch_fields(hf: dict) -> dict:
    """The program's ``ArchConfig`` fields for this configuration."""
    m = dims(hf)
    swa = hf.get("sliding_window")
    return {"family": "dense", "n_layers": m["L"], "d_model": m["d"],
            "n_heads": m["h"], "n_kv_heads": m["hkv"], "d_ff": m["f"],
            "vocab": m["v"], "head_dim": m["hd"],
            "attn_kind": "swa" if swa else "full",
            "window": int(swa or 4096),
            "rope_theta": float(hf["rope_theta"]),
            "norm_eps": float(hf["rms_norm_eps"]),
            "act": "silu", "tie_embeddings": False}


def matmul_params(hf: dict) -> int:
    """Weights every token multiplies by (the embedding is a lookup)."""
    m = dims(hf)
    d, h, hkv, hd, f, v, L = (m[k] for k in ("d", "h", "hkv", "hd", "f",
                                              "v", "L"))
    return L * (d * h * hd + 2 * d * hkv * hd + h * hd * d + 3 * d * f) \
        + d * v


def token_flops(hf: dict, context: int) -> float:
    """Useful FLOPs of one token that attends to ``context`` positions
    (itself included): every matmul once, and QK^T plus AV over the
    context. Parity, padding and masked cache positions do not count."""
    m = dims(hf)
    return 2.0 * matmul_params(hf) \
        + 4.0 * m["L"] * m["h"] * m["hd"] * context


def prefill_flops(hf: dict, n: int) -> float:
    """Useful FLOPs of a prompt of ``n`` tokens: every position through
    the layers, causal attention, and the head for the last position."""
    m = dims(hf)
    per_tok = 2.0 * (matmul_params(hf) - m["d"] * m["v"])
    attn = 4.0 * m["L"] * m["h"] * m["hd"] * n * (n + 1) / 2
    return per_tok * n + attn + 2.0 * m["d"] * m["v"]
