"""Share of the window in which the serve loop had nothing queued or
active (the sum of its ``serve/idle`` spans), in %. Read beside
``device_idle.code``, it splits the device's idle time into no work and
the host holding the chip."""
import spans


def read(ctx):
    if not spans.spans("serve/enqueue"):
        return None
    idle = sum(d for _, d, _ in spans.spans("serve/idle"))
    return 100.0 * idle / ctx.window_s
