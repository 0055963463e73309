"""Mean device time of one prefill (layer ``prefill`` of layers.json: the
prefill program and the scatter of its KV into the page pool), in ms."""


def read(ctx):
    t, n = ctx.device_trace, ctx.observed.get("n_prefills", 0)
    s = t.module_s("prefill") if t else 0.0
    return 1e3 * s / n if s > 0 and n else None
