"""tokens_per_s: generated tokens delivered in the window over its wall
time (whole chunks, delivery to delivery)."""


def read(w):
    return w.tokens() / w.seconds
