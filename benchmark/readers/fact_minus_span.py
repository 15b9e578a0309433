"""One of the run's own timings per item (a fact, already in the metric's
unit) minus a program span's mean inside the window times ``scale``: what
the item cost outside that span."""


def read(window, facts, fact: str, family: str, scale: float = 1.0):
    total, count = window.span_delta(family)
    if not count or facts.get(fact) is None:
        return None
    return facts[fact] - total / count * scale
