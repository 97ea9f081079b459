"""Device time of one decode tick run in the model's ``ffn`` scope
(``models/lm.py``: norm2 + the MLP's tile matvec calls), in ms, from the
trace (``harness/scopes.py``)."""
from harness.scopes import device_ms


def read(run):
    return device_ms(run, "decode_tick", "ffn")
