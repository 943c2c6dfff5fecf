"""entry points: percent of the window under the spans of a history's
way to the device: `linearizable.lint`, `.encode`, `.prep` (search
encoding, dims, reductions, padding), `.witness` (greedy witness), and
`independent.encode`; None where the program records none of them."""

from core.readers import span_share

NAMES = {"linearizable.lint", "linearizable.encode", "linearizable.prep",
         "linearizable.witness", "independent.encode"}


def read(ctx):
    if not any(s["name"] in NAMES for s in ctx["spans"]):
        return None
    return span_share(ctx, NAMES)
