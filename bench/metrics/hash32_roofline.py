"""Percent of the HBM roofline reached by ``kops.hash32``: the exchange's
row hash, one key column read and one uint32 column written per call
(``bench.roofline``: the calls' logical bytes over their device time
times the chip's HBM bytes per second)."""
from bench import roofline


def read(summary):
    return roofline.share(summary, "hash32")
