"""Reader ``span``: the summed duration of one of the program's spans
(kueue_tpu/obs/trace.py) inside the measured window.  Spec: ``span``,
``per`` and ``scale``.  With the tracer off there is nothing to read.
With it on, a span site that was never entered spent no time: with
``zero_when_absent`` that reads 0, and without it nothing."""

from ._per import divisor


def read(spec: dict, ctx: dict):
    if not ctx.get("tracer_on"):
        return None
    total = ctx["spans"].get(spec["span"])
    if total is None and spec.get("zero_when_absent"):
        total = 0.0
    n = divisor(spec, ctx)
    if total is None or n is None:
        return None
    return total / n * spec.get("scale", 1.0)
