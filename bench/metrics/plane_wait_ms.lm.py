"""plane_wait_ms.lm: milliseconds the data plane blocks waiting for shard
loads (``plane.wait`` spans, inside ``stage.acquire``) in the traced window
over the stages begun in it, counted as ``acquire_ms.lm`` counts them."""
LAYER = "data plane (data/)"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "tokens_per_s"


def read(cell, rec):
    wait = [d for n, _, d in rec.spans if n == "plane.wait"]
    begun = sum(1 for n, _, _ in rec.spans if n == "stage.begin")
    if not wait or not begun:
        return None
    return 1000.0 * sum(wait) / begun
