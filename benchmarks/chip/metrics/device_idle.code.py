"""Share of the traced window in which no program ran on the device, in
%, averaged over the cell's chips."""


def read(ctx):
    t = ctx.device_trace
    return 100.0 * t.idle_share if t and t.busy_s > 0 else None
