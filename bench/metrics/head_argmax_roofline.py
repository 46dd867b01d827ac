"""head_argmax_roofline: ``cdc_fused_head_argmax_pallas`` (coded LM head,
sum-parity decode and greedy argmax) against its roofline; bound by
memory at 16 rows."""
from bench.metrics._roofline import roofline


def read(run):
    return roofline(run, "cdc_fused_head_argmax_pallas")
