"""optimizer_ms.lm: milliseconds of device self time per optimizer step in
the AdamW update and the gradient norm (ops under ``bet.optimizer``), from
the traced window's op times and the program's map of the compiled stage
kernel (``bench/scopes.py``)."""
from bench import scopes

LAYER = "optimizer (AdamW)"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "tokens_per_s"


def read(cell, rec):
    return scopes.step_ms(rec, "optimizer")
