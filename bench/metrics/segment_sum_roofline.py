"""segment_sum kernel: share of the HBM roofline."""
from bench.costs import segment_sum_bytes
from bench.readers import roofline


def read(run):
    return roofline(run, "segment_sum", segment_sum_bytes)
