"""Stream MTTKRP's share of its roofline, in %: the least time of a
sweep's N MTTKRPs on this chip (counts.mttkrp_least_s: useful operations
over the bf16 peak, or useful bytes over HBM bandwidth, whichever is
longer) over their device time per sweep."""
import counts
import harness
import peaks


def read(ctx):
    t = ctx.device_trace
    s = t.module_s("stream_mttkrp") if t else 0.0
    if s <= 0:
        return None
    o = ctx.observed
    p = peaks.peaks(ctx.devices[0].device_kind)
    least, side = counts.mttkrp_least_s(o["nnz"], o["dims"], o["rank"],
                                        p["bf16_flops"], p["hbm_bytes_per_s"])
    harness.log(f"[trace] stream MTTKRP least {least * 1e3:.4f} ms per sweep,"
                f" {side}-bound")
    return 100.0 * least / (s / o["sweeps"])
