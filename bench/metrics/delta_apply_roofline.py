"""Delta-apply kernels (chain and fused): share of the HBM roofline."""
from bench.costs import delta_apply_bytes
from bench.readers import roofline


def read(run):
    return roofline(run, "delta_apply", delta_apply_bytes)
