"""Reader ``counter``: the growth of one or more of the program's
counters (or of JAX's compile events) in a phase of the run.  Spec:
``counters`` (summed), ``phase`` (``window`` or ``setup``), ``per`` and
``scale``.  A counter the program does not have is nothing to read."""

from ._per import divisor


def read(spec: dict, ctx: dict):
    have = ctx["counters"][spec.get("phase", "window")]
    if any(c not in have for c in spec["counters"]):
        return None
    n = divisor(spec, ctx)
    if n is None:
        return None
    return sum(have[c] for c in spec["counters"]) / n * spec.get("scale", 1.0)
