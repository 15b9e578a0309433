"""One span family's seconds minus another's inside the window (a span's
self time where the second nests in the first), over one of the run's
facts, times ``scale``."""


def read(window, facts, family: str, minus: str, per: str, scale: float = 1.0):
    outer, n_outer = window.span_delta(family)
    inner, _ = window.span_delta(minus)
    denom = facts.get(per, 0)
    if not n_outer or not denom:
        return None
    return (outer - inner) / denom * scale
