"""Device time per sweep of the stream-MTTKRP programs (layer
``stream_mttkrp`` of layers.json), in ms."""


def read(ctx):
    t = ctx.device_trace
    s = t.module_s("stream_mttkrp") if t else 0.0
    return 1e3 * s / ctx.observed["sweeps"] if s > 0 else None
