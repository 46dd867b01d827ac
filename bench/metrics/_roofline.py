"""Shared by the kernel roofline readers: the least time the chip needs
for the kernel's calls in the traced window (operations and bytes from
shapes, bench/costs.py) over the kernel's summed device time."""
from bench import costs


def roofline(run, kernel: str):
    if run.trace is None or run.peaks is None:
        return None
    secs, calls = run.trace.kernel(kernel)
    if not calls or secs <= 0:
        return None
    code = run.serving["code"]
    per_round = costs.round_kernel_calls(run.hf, run.serving["n_slots"],
                                         code["T"], code["r"])[kernel]
    least, _ = costs.min_seconds(per_round, run.peaks)
    return 100.0 * calls * (least / len(per_round)) / secs
