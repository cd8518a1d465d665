"""compiles_in_window: backend compiles whose end falls inside the window
(JAX's monitoring events, ``bench/compiles.py``).  0 when the warm-up
covered every shape the window used."""


def read(w):
    return w.compiles
