"""Reduce a profiler trace of the window to the per-layer numbers.

``reduce_dir`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote,
with nothing but JAX's own reader, into two lists on one clock: the
operations that ran on the first device (its ``XLA Ops`` line) and the
host spans the benchmark annotated (``bench.*``; the executor's
``decode_round`` steps too). ``Summary`` does the arithmetic on those
lists, so tests can build them by hand:

* the traced window: first ``bench.*`` span start to last span end;
* busy: the union of the operations' intervals inside the window;
  idle share = 1 - busy / window;
* a kernel's time: the summed durations of the operations whose name
  holds its stable name (``cdc_coded_matmul_pallas`` ...), and their
  count;
* idle gaps, longest first, each named by the innermost host span that
  covers its middle.

The device line nests operations: a ``while`` (a scan over layers)
spans the operations of its body. Unions are immune to that; the list of
the costliest operations leaves the control-flow containers out.
"""
from __future__ import annotations

import dataclasses
import glob
import os

CONTAINERS = ("while", "conditional", "call")   # ops that nest others


@dataclasses.dataclass(frozen=True)
class Op:
    name: str
    module: str
    start: float            # seconds on the trace's clock
    dur: float


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start: float
    end: float
    args: tuple = ()


def _union(intervals):
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


@dataclasses.dataclass
class Summary:
    ops: list
    spans: list

    def __post_init__(self):
        bench = [s for s in self.spans if s.name.startswith("bench.")]
        if bench:
            self.t0 = min(s.start for s in bench)
            self.t1 = max(s.end for s in bench)
        elif self.ops:
            self.t0 = min(o.start for o in self.ops)
            self.t1 = max(o.start + o.dur for o in self.ops)
        else:
            self.t0 = self.t1 = 0.0
        clipped = [(max(o.start, self.t0), min(o.start + o.dur, self.t1))
                   for o in self.ops]
        self.busy = _union([(a, b) for a, b in clipped if b > a])

    # ------------------------------------------------------- device --
    @property
    def window_s(self) -> float:
        return self.t1 - self.t0

    @property
    def busy_s(self) -> float:
        return sum(b - a for a, b in self.busy)

    def idle_share(self) -> float | None:
        if self.window_s <= 0 or not self.busy:
            return None
        return 1.0 - self.busy_s / self.window_s

    def gaps(self) -> list[tuple[float, float]]:
        """Idle intervals inside the window, longest first."""
        edges = [self.t0] + [x for ab in self.busy for x in ab] + [self.t1]
        out = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
               if edges[i + 1] > edges[i]]
        return sorted(out, key=lambda g: g[0] - g[1])

    def host_at(self, t: float) -> str:
        inner = None
        for s in self.spans:
            if s.start <= t < s.end and (inner is None
                                         or s.start >= inner.start):
                inner = s
        return inner.name if inner else "no host span"

    # ------------------------------------------------------ kernels --
    def kernel(self, name: str) -> tuple[float, int]:
        """(seconds, calls) of the operations named after ``name``."""
        mine = [o for o in self.ops if name in o.name]
        return sum(o.dur for o in mine), len(mine)

    def breakdown(self, n: int = 10) -> dict:
        by_name: dict[str, float] = {}
        for o in self.ops:
            if o.name.split(".")[0] in CONTAINERS:
                continue
            key = f"{o.module}/{o.name}" if o.module else o.name
            by_name[key] = by_name.get(key, 0.0) + o.dur
        top = sorted(by_name.items(), key=lambda kv: -kv[1])[:n]
        gaps = [[self.host_at((a + b) / 2), b - a]
                for a, b in self.gaps()[:n]]
        return {"device_ops": [[k, v] for k, v in top], "idle_gaps": gaps}


# ------------------------------------------------------- the xplane --

def _stats(ev) -> dict:
    return {k: v for k, v in ev.stats}


def op_name(text: str) -> str:
    """``%cdc_coded_matmul_pallas.66 = bf16[...] custom-call(...)`` ->
    ``cdc_coded_matmul_pallas.66``: the device line names each
    operation by its HLO text."""
    head = text.split(" = ", 1)[0]
    return head[1:] if head.startswith("%") else head


def load(path: str, device: int = 0) -> Summary:
    """Ops of device ``device``, each with the program (``XLA Modules``
    event, fingerprint dropped) it ran in, and the host's annotated
    spans."""
    import bisect

    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    raw, mods, spans = [], [], []
    for plane in pd.planes:
        if plane.name == f"/device:TPU:{device}":
            for line in plane.lines:
                if line.name == "XLA Modules":
                    mods = [(ev.start_ns * 1e-9, ev.name.split("(")[0])
                            for ev in line.events]
                elif line.name == "XLA Ops":
                    raw = [(op_name(ev.name), ev.start_ns * 1e-9,
                            ev.duration_ns * 1e-9) for ev in line.events]
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench.") \
                            or ev.name == "decode_round":
                        t = ev.start_ns * 1e-9
                        spans.append(Span(
                            ev.name, t, t + ev.duration_ns * 1e-9,
                            tuple(sorted(_stats(ev).items()))))
    mods.sort()
    starts = [m[0] for m in mods]
    ops = []
    for name, t, dur in raw:
        i = bisect.bisect_right(starts, t) - 1
        ops.append(Op(name, mods[i][1] if i >= 0 else "", t, dur))
    return Summary(ops, spans)


def reduce_dir(trace_dir: str) -> Summary | None:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return load(sorted(paths)[-1]) if paths else None
