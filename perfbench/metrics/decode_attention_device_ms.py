"""Device time of one decode tick run in the model's ``attn`` scope
(``models/lm.py``: norm1 + attention, the paged KV gather and scatter
included), in ms, from the trace (``harness/scopes.py``)."""
from harness.scopes import device_ms


def read(run):
    return device_ms(run, "decode_tick", "attn")
