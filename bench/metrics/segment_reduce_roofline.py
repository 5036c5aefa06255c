"""Percent of the HBM roofline reached by ``kops.segment_reduce``: the
groupby's segmented sums, by the Pallas kernel or the XLA scatter,
whichever runs (``bench.roofline``: the calls' logical bytes over their
device time times the chip's HBM bytes per second). Where a program runs
both routes the share is of both together, and the calls with the most
rows weigh most: in q12 the first phase's XLA scatter over the join's
output slots, beside a kernel call over the few merged partial groups."""
from bench import roofline


def read(summary):
    return roofline.share(summary, "_segment_reduce_jit")
