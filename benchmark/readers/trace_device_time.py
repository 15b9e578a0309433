"""Device-operation self time in the traced stretch of the window (summed
over operations whose name matches ``select``, all if none; averaged over
the chips), over the items that were whole inside that stretch, times
``scale``.  Nothing without a trace."""

import re


def read(window, facts, scale: float = 1.0, select: str | None = None):
    trace, traced = window.trace, window.traced
    if trace is None or not traced or not traced.get("items"):
        return None
    pick = re.compile(select) if select else None
    total = sum(s for name, s in trace["ops"] if pick is None or pick.search(name))
    return total / traced["items"] * scale
