"""Reader ``clock``: a time the benchmark took itself, on the host's
clock, around a call into the program.  Spec: ``clock`` (a key of the
run's clocks, seconds), ``per`` and ``scale``."""

from ._per import divisor


def read(spec: dict, ctx: dict):
    total = ctx["clocks"].get(spec["clock"])
    n = divisor(spec, ctx)
    if total is None or n is None:
        return None
    return total / n * spec.get("scale", 1.0)
