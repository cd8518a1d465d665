"""slot_occupancy: decode tokens over (decode steps x slots) in the window,
as a percentage.  Both are counts: the tokens of the registry counter
``serve_generated_tokens``, the steps of the tracer's ``chunk`` spans."""


def read(w):
    steps = sum(c.steps for c in w.chunks())
    return 100.0 * w.tokens() / (steps * w.slots) if steps else None
