"""backward_ms.lm: milliseconds of device self time per optimizer step in
the training step's backward pass, remat's recompute included (ops under
``transpose(...bet.forward...)``), from the traced window's op times and the
program's map of the compiled stage kernel (``bench/scopes.py``)."""
from bench import scopes

LAYER = "model step (backward, remat recompute included)"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "tokens_per_s"


def read(cell, rec):
    return scopes.step_ms(rec, "backward")
