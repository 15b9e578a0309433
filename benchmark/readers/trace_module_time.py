"""Device time of whole XLA modules (one per jitted program) in the traced
stretch of the window, summed over the modules whose name matches
``select`` (all if none; averaged over the chips), over the items that
were whole inside that stretch, times ``scale``.  As ``trace_device_time``,
but over the trace's modules line, not its operations: a chain stage is a
module of its own.  Nothing without a trace, and nothing where no module
matches."""

import re


def read(window, facts, scale: float = 1.0, select: str | None = None):
    trace, traced = window.trace, window.traced
    if trace is None or not traced or not traced.get("items"):
        return None
    pick = re.compile(select) if select else None
    times = [s for name, s in trace.get("modules", ())
             if pick is None or pick.search(name)]
    if not times:
        return None
    return sum(times) / traced["items"] * scale
