"""A program span's seconds inside the window (``_sum`` of its histogram
family, never a bucket quantile) over its own ``_count`` or over one of
the run's facts, times ``scale``."""


def read(window, facts, family: str, per: str | None = None, scale: float = 1.0):
    total, count = window.span_delta(family)
    denom = count if per is None else facts.get(per, 0)
    if not count or not denom:
        return None
    return total / denom * scale
