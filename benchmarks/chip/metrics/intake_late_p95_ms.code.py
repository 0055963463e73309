"""95th percentile (nearest rank) of how late the loop's producer released
a request: ``late_ms`` of each ``serve/enqueue`` span, the enqueue time
minus the request's due time, in ms."""
import spans


def read(ctx):
    late = [a["late_ms"] for _, _, a in spans.spans("serve/enqueue")]
    return spans.pct(late, 95) if late else None
