"""probe_ms.lm: milliseconds of device self time per optimizer step in the
full-data probe forward the stage kernel runs after each step (ops under
``bet.probe``), from the traced window's op times and the program's map of
the compiled stage kernel (``bench/scopes.py``)."""
from bench import scopes

LAYER = "probe forward"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "tokens_per_s"


def read(cell, rec):
    return scopes.step_ms(rec, "probe")
