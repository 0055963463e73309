"""A sweep's useful operations (counts.cp_als_sweep_ops) over sweep_s
times the chip's bf16 peak, in %."""
import counts
import peaks


def read(ctx):
    o = ctx.observed
    sweep_s = ctx.end_to_end.get("sweep_s")
    if not sweep_s:
        return None
    p = peaks.peaks(ctx.devices[0].device_kind)
    ops = counts.cp_als_sweep_ops(o["nnz"], o["dims"], o["rank"])
    return 100.0 * ops / (sweep_s * p["bf16_flops"])
