"""Reader ``memory``: the peak bytes in use on the fullest chip, as
``device.memory_stats()["peak_bytes_in_use"]`` gives it after the
window: the same number as ``device.memory_peak_bytes``."""


def read(spec: dict, ctx: dict):
    peak = ctx.get("memory_peak_bytes")
    if not peak:
        return None
    return peak * spec.get("scale", 1.0)
