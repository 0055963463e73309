"""Host time per decode step outside the device call: the mean over
``serve/step`` spans of the step's duration minus its ``serve/decode``
child (dispatch and the logits' read), in ms."""
import spans


def read(ctx):
    steps = spans.spans("serve/step")
    if not steps:
        return None
    kids = spans.children(steps, spans.spans("serve/decode"))
    host = [d - sum(c[1] for c in k) for (_, d, _), k in zip(steps, kids)]
    return 1e3 * sum(host) / len(host)
