"""cdc_matmul_roofline: ``cdc_coded_matmul_pallas`` (the in-body coded
GEMMs: QKV and FFN gate/up with their parity and Eq. 12 decode) against
its roofline. At 16 rows it is bound by memory: the weights and the
f32 parity are read once per call."""
from bench.metrics._roofline import roofline


def read(run):
    return roofline(run, "cdc_coded_matmul_pallas")
