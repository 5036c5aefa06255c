"""Percent of the HBM roofline reached by ``kops.segment_reduce`` in TPC-H
Q1: the groupby's five float32 sums and its count over the filtered
lineitem rows (``bench.roofline``: the calls' logical bytes over their
device time times the chip's HBM bytes per second), on whichever route
runs them."""
from bench import roofline


def read(summary):
    return roofline.share(summary, "_segment_reduce_jit")
