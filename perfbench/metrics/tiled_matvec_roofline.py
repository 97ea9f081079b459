"""Decode-path tile kernel (``kernels/tiled_matvec.py``): the least time
its traced calls could take on the chip over the device time they took,
in %. Counted by unique rows (``harness/counting.py``)."""
from harness.kernels import roofline


def read(run):
    return roofline(run, "tiled_matvec")
