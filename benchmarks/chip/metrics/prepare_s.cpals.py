"""Host preparation of the window's ``cp_als`` call, from its entry to its
first sweep (the ``als/prepare`` span: the dedupe sort of the nonzeros,
the norm, the initial factors and their Grams), in s."""
import spans


def read(ctx):
    prep = spans.spans("als/prepare")
    return prep[-1][1] if prep else None
