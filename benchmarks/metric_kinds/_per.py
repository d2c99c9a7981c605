"""Shared by the readers: the divisor a spec names under ``per``."""


def divisor(spec: dict, ctx: dict):
    per = spec.get("per", "window")
    n = {"round": ctx["rounds"], "cycle": ctx["cycles"], "window": 1}[per]
    return n if n else None
