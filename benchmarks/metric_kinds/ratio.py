"""Reader ``ratio``: the share of one sum of the program's counters that
another does not cover, 100 x (1 - sum of ``over`` / sum of ``under``),
over their growth in a phase of the run (``phase``: ``window`` or
``setup``).  Spec: ``over`` and ``under``, lists of counters.  A counter
the program does not have, or an ``under`` that did not grow, is nothing
to read."""


def read(spec: dict, ctx: dict):
    have = ctx["counters"][spec.get("phase", "window")]
    if any(c not in have for c in spec["over"] + spec["under"]):
        return None
    under = sum(have[c] for c in spec["under"])
    if not under:
        return None
    return 100.0 * (1.0 - sum(have[c] for c in spec["over"]) / under)
