"""The comparison that decides ``correct``.

After the window has closed and the program's state is freed, a sample
drawn from the seed of the requests the run served is run once through
the plain float32 reference: each prompt followed by its served tokens.
The sample holds one request from every slot the run used (so a fault
in one slot is caught), the longest request, and in a mix with an
erasure one that decoded across it and one admitted after it. At every
served position the gap is the reference's best logit minus the
reference's logit of the token the program served; ``max_logit_gap`` is
the widest gap over the sample. Served tokens come from the timed path
itself: the first from admission (the prefill program), the rest from
the fused decode rounds, some after the erasure.

The control (``control=True``, for the tools and tests, never in a
benchmark run) puts the reference in the program's place computed in
int8 (W8A8): at the same positions it reads the gap of the token the
int8 forward puts first. Its reading goes through the same rows and
limits as the program's (``compared``), and has to come out not
correct. Beside the widest gap each side also reports the mean gap and
the share of positions off the reference's best.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def sample(records, seed: int, n: int, erase_ms: float | None) -> list:
    """Served requests to compare: the longest; one from every slot that
    served (a finished one where the slot finished any); with an
    erasure, one that decoded across it and one admitted after it; then
    others up to ``n``, all drawn from the seed. A backlog's window
    closes on requests still decoding, and their served tokens count."""
    served = [r for r in records if len(r.tokens) > 1]
    if not served:
        return []
    rng = np.random.default_rng([seed % 2 ** 63, 7])
    key = lambda r: (len(r.prompt) + len(r.tokens), -r.rid)  # noqa: E731
    picked = [max(served, key=key)]

    def draw(group):
        group = [r for r in group if r not in picked]
        if group:
            picked.append(group[int(rng.integers(len(group)))])

    for slot in sorted({r.slot for r in served}):
        in_slot = [r for r in served if r.slot == slot]
        if not any(r in picked for r in in_slot):
            draw([r for r in in_slot if r.done] or in_slot)
    if erase_ms is not None:
        draw([r for r in served if r.first_ms < erase_ms < r.token_ms[-1]])
        draw([r for r in served if r.admit_ms is not None
              and r.admit_ms >= erase_ms])
    rest = [r for r in served if r not in picked]
    k = max(0, min(n - len(picked), len(rest)))
    for i in rng.choice(len(rest), size=k, replace=False):
        picked.append(rest[int(i)])
    return picked


@jax.jit
def _gaps(logits, served):
    """Per position: best logit minus the served token's logit."""
    best = logits.max(-1)
    got = jnp.take_along_axis(logits, served[:, None], -1)[:, 0]
    return best - got


def compare(ref, hf: dict, weights: dict, picked: list, length: int,
            control: bool = False) -> dict:
    """Run the reference over each picked request (padded to ``length``
    so one program serves every request; padding follows the sequence,
    so causal attention never sees it)."""
    gaps, ctrl = [], []
    for rec in picked:
        toks = np.asarray(rec.tokens, np.int32)
        seq = np.concatenate([np.asarray(rec.prompt, np.int32), toks[:-1]])
        if len(seq) > length:
            raise ValueError(f"request of {len(seq)} tokens is longer than "
                             f"the cache length {length}")
        padded = np.zeros(length, np.int32)
        padded[:len(seq)] = seq
        p = len(rec.prompt)
        pos = np.arange(p - 1, p - 1 + len(toks))
        logits = ref.forward(weights, hf, padded)[pos]
        gaps.append(np.asarray(_gaps(logits, jnp.asarray(toks))))
        if control:
            lc = ref.forward(weights, hf, padded, control=True)[pos]
            ctrl.append(np.asarray(_gaps(logits, jnp.argmax(lc, -1))))
        del logits
    out = _summary(picked, gaps)
    if control:
        out["control"] = _summary(picked, ctrl)
    return out


def _summary(picked: list, gaps: list) -> dict:
    """The widest gap, the mean gap and the share of positions whose
    token is not the reference's best, over every compared position."""
    out = {"requests_compared": len(picked),
           "tokens_compared": int(sum(len(g) for g in gaps))}
    if not gaps:
        return {**out, "max_logit_gap": float("nan")}
    g = np.concatenate(gaps)
    return {**out, "max_logit_gap": float(g.max()),
            "mean_logit_gap": float(g.mean()),
            "mismatch_share": float((g > 0).mean())}


def compared(check: dict, limits: dict, n_failed: int, min_tokens: int
             ) -> dict:
    """Each number compared, with its limit and whether it keeps it: the
    program's reading, or the control's (``check["control"]``)."""
    rows = {
        "max_logit_gap": {"value": check["max_logit_gap"],
                          "limit": limits["max_logit_gap"], "keep": "<="},
        "tokens_compared": {"value": check["tokens_compared"],
                            "limit": min_tokens, "keep": ">="},
        "requests_unfinished": {"value": n_failed, "limit": 0,
                                "keep": "<="},
    }
    for row in rows.values():
        v, lim = row["value"], row["limit"]
        row["ok"] = bool(np.isfinite(v)) and (
            v <= lim if row["keep"] == "<=" else v >= lim)
    return rows
