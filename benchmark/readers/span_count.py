"""How often a program span (or an ``observe``d histogram) was recorded
inside the window: the family's ``_count`` gained there, over one of the
run's facts (``bursts``, ``aggregates``, ``blocks``), times ``scale``.
Nothing where the family was never recorded."""


def read(window, facts, family: str, per: str, scale: float = 1.0):
    _total, count = window.span_delta(family)
    denom = facts.get(per, 0)
    if not count or not denom:
        return None
    return count / denom * scale
