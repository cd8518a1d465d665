"""upload_gbps: layer weight bytes handed to the device over the time of
the hand-offs, GB/s.  A hand-off is a ``pcie``/``w`` lane span with no byte
count (the streamer's ``device_put`` + ``block_until_ready`` of one staged
layer); each moves one layer's weights, counted from the configuration's
sizes (``Window.counts``).  Offload cells only."""


def handoffs(w):
    return [s for s in w.tap.lanes if s.name == "pcie/w"
            and s.args["nbytes"] == 0 and w.inside(s.end)]


def read(w):
    h = handoffs(w)
    secs = sum(s.end - s.start for s in h)
    if not h or secs <= 0:
        return None
    return len(h) * w.counts.layer_bytes(w.config) / secs / 1e9
