"""Prefill's share of the chip's bf16 peak, in %: the model FLOPs of the
window's prompts (counts.prefill_flops, unpadded) over the prefill
programs' device time."""
import peaks


def read(ctx):
    t = ctx.device_trace
    s = t.module_s("prefill") if t else 0.0
    if s <= 0:
        return None
    p = peaks.peaks(ctx.devices[0].device_kind)
    return 100.0 * ctx.observed["prefill_flops"] / (s * p["bf16_flops"])
