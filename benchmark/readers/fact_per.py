"""One of the run's facts over another (``bisect_checks`` over ``bursts``),
times ``scale``: a count the generator took from the program's counters
inside the window, per item.  Nothing where the generator did not book the
fact or no item was whole."""


def read(window, facts, fact: str, per: str, scale: float = 1.0):
    value, denom = facts.get(fact), facts.get(per, 0)
    if value is None or not denom:
        return None
    return value / denom * scale
