"""Seconds from the start of the benchmark process to the start of the
window: drawing the weights, building the engine, compiling (or loading)
its tick executables, and the warm-up requests."""


def read(run):
    return run.setup_s
