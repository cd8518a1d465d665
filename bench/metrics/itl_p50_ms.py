"""itl_p50_ms: median, over every token delivered in the window, of the time
since the same request's previous token (0 after the first of the tokens one
chunk delivers together), in milliseconds.  The median, not a tail: a
window of the offload cells delivers ~70 tokens, too few for a 95th
percentile, which there swings between the step time and an admission
stall with the number of admissions that fall in the window."""
import numpy as np


def read(w):
    g = w.gaps()
    return float(np.percentile(g, 50)) * 1e3 if len(g) else None
