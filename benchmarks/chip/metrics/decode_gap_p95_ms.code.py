"""What a live row waits between decode steps: the 95th percentile
(nearest rank) over consecutive ``serve/step`` spans with no
``serve/idle`` between them of the next step's start minus the previous
step's end, in ms. Prefills that the loop runs between steps fill it."""
import bisect

import spans


def read(ctx):
    steps = spans.spans("serve/step")
    idle_starts = [t for t, _, _ in spans.spans("serve/idle")]
    gaps = []
    for (t0, d0, _), (t1, _, _) in zip(steps, steps[1:]):
        end = t0 + d0
        i = bisect.bisect_left(idle_starts, end)
        if i == len(idle_starts) or idle_starts[i] >= t1:
            gaps.append(t1 - end)
    return 1e3 * spans.pct(gaps, 95) if gaps else None
