"""Percent of the HBM roofline reached by ``kops.bucket_histogram``: the
exchange's count of rows per destination, one id column read per call
(``bench.roofline``: the calls' logical bytes over their device time
times the chip's HBM bytes per second)."""
from bench import roofline


def read(summary):
    return roofline.share(summary, "bucket_histogram")
