"""setup_s: process start to window open (weights, warm-up, ramp), seconds."""


def read(w):
    return w.setup_s
