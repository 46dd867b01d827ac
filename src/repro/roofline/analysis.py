"""Roofline terms from a compiled dry-run artifact (no real hardware).

Three terms, in seconds, for the per-device program (the SPMD-partitioned
HLO module IS the per-device program, so no /chips rescale is needed —
equivalent to the spec's total/(chips*peak) form):

  compute    = HLO_FLOPs / peak_FLOPs
  memory     = HLO_bytes_accessed / HBM_bw
  collective = wire_bytes / link_bw

wire_bytes comes from parsing the compiled HLO: every all-gather /
all-reduce / reduce-scatter / all-to-all / collective-permute op contributes
ring-model bytes:
  all-gather:    out_bytes * (k-1)/k        (receives all but own slice)
  all-reduce:    2 * bytes * (k-1)/k        (reduce-scatter + all-gather)
  reduce-scatter: in_bytes * (k-1)/k  = out_bytes * (k-1)
  all-to-all:    bytes * (k-1)/k
  collective-permute: bytes
Hardware: one row of ``PEAKS`` per ``jax.Device.device_kind``.
"""
from __future__ import annotations

import dataclasses
import re

import numpy as np

#: Published per-chip peaks, keyed by ``device_kind``. TPU v5e ("TPU v5
#: lite"), from Google Cloud's "TPU v5e" documentation page: 197 TFLOP/s
#: bf16, 16 GB HBM at 819 GB/s, 1,600 Gbit/s of chip-to-chip interconnect
#: over 4 ICI links (50 GB/s each).
PEAKS: dict[str, dict[str, float]] = {
    "TPU v5 lite": {
        "peak_flops": 197e12,   # bf16
        "hbm_bw": 819e9,        # bytes/s
        "link_bw": 50e9,        # bytes/s per ICI link
    },
}

#: The design target of the compile-only dry-run (a v5e fleet).
HW = PEAKS["TPU v5 lite"]


def device_peaks(device=None) -> dict[str, float] | None:
    """Peaks of ``device`` (default: the first JAX device). None off the
    TPU — a CPU has no roofline share here; a TPU kind missing from
    ``PEAKS`` raises rather than borrow another chip's numbers."""
    if device is None:
        import jax
        device = jax.devices()[0]
    if device.platform != "tpu":
        return None
    if device.device_kind not in PEAKS:
        raise KeyError(f"no published peaks for TPU kind "
                       f"{device.device_kind!r}; add its row to "
                       f"repro.roofline.analysis.PEAKS")
    return PEAKS[device.device_kind]

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLL_RE = re.compile(
    r"=\s*(?:\(([^)]*)\)|(\S+))\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(", )
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\{\{([\d,]+)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]<=")


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(shape_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict:
    """Ring-model wire bytes per collective kind, from compiled HLO text."""
    out: dict[str, float] = {"all-gather": 0.0, "all-reduce": 0.0,
                             "reduce-scatter": 0.0, "all-to-all": 0.0,
                             "collective-permute": 0.0}
    counts: dict[str, int] = dict.fromkeys(out, 0)
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        shape_str = m.group(1) or m.group(2)
        kind = m.group(3)
        if "-done" in line.split("=")[1][:60]:
            continue  # the -start op already counted
        bytes_ = _shape_bytes(shape_str)
        gm = _GROUPS_RE.search(line)
        if gm:
            k = len(gm.group(1).split(","))
        else:
            gi = _GROUPS_IOTA_RE.search(line)
            k = int(gi.group(2)) if gi else 2
        k = max(k, 2)
        if kind == "all-gather":
            wire = bytes_ * (k - 1) / k
        elif kind == "all-reduce":
            wire = 2 * bytes_ * (k - 1) / k
        elif kind == "reduce-scatter":
            wire = bytes_ * (k - 1)  # out is 1/k of input
        elif kind == "all-to-all":
            wire = bytes_ * (k - 1) / k
        else:  # collective-permute
            wire = bytes_
        out[kind] += wire
        counts[kind] += 1
    out["total"] = sum(out.values())
    out["counts"] = counts
    return out


def roofline_terms(cost: dict, coll: dict, hw: dict = HW) -> dict:
    flops = float(cost.get("flops", 0.0))
    bytes_ = float(cost.get("bytes accessed", 0.0))
    wire = float(coll.get("total", 0.0))
    t_c = flops / hw["peak_flops"]
    t_m = bytes_ / hw["hbm_bw"]
    t_x = wire / hw["link_bw"]
    dom = max(("compute", t_c), ("memory", t_m), ("collective", t_x),
              key=lambda kv: kv[1])[0]
    tot = max(t_c, t_m, t_x)
    return {
        "compute_s": t_c, "memory_s": t_m, "collective_s": t_x,
        "dominant": dom,
        "bound_step_s": tot,
        "flops": flops, "bytes": bytes_, "wire_bytes": wire,
    }


def roofline_report(terms: dict, model_flops_per_device: float) -> dict:
    """Adds MODEL_FLOPS/HLO_FLOPs usefulness ratio and roofline fraction."""
    hlo_flops = terms["flops"]
    useful = model_flops_per_device / hlo_flops if hlo_flops else 0.0
    # fraction of the dominant-roofline bound that useful compute achieves
    t_useful = model_flops_per_device / HW["peak_flops"]
    frac = t_useful / terms["bound_step_s"] if terms["bound_step_s"] else 0.0
    return dict(terms, model_flops=model_flops_per_device,
                useful_ratio=useful, roofline_fraction=frac)
