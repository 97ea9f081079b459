"""Device time of one decode tick run in operations that no named scope
of the program claims (copies the compiler inserted, which carry no
metadata), in ms, from the trace (``harness/scopes.py``)."""
from harness.scopes import UNSCOPED, device_ms


def read(run):
    return device_ms(run, "decode_tick", UNSCOPED)
