"""Device time per sweep of the fit's exact last-mode MTTKRP (layer
``fit`` of layers.json), in ms."""


def read(ctx):
    t = ctx.device_trace
    s = t.module_s("fit") if t else 0.0
    return 1e3 * s / ctx.observed["sweeps"] if s > 0 else None
