"""device_idle_share: 1 - (union of device operation intervals) / traced
window, as a percentage, averaged over the chips used."""
from bench import devtrace


def read(w):
    if not w.trace:
        return None
    busy = devtrace.busy_seconds(w.trace)
    return 100.0 * (1.0 - busy / w.trace_window_s) if busy > 0 else None
