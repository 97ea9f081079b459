"""Device time of one run of the engine's extend tick executable
(``serve/engine.py`` ``_extend_tick`` over ``models/lm.py``), in ms,
from the trace."""
from harness.ticks import device_ms


def read(run):
    return device_ms(run, "extend_tick")
