"""Whole decode step's share of the chip's peak bf16 FLOP/s, in %: the
operations the window's decode calls needed, counted by unique tile rows,
over the host-clock time of the benchmark's spans around those calls."""
from harness.ticks import mfu


def read(run):
    return mfu(run, "decode_tick")
