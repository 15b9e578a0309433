"""Per-layer metric readers, found by the ``reader`` name in
``layers/<metric>.json``.  Each has ``read(window, facts, **args)`` and
returns a number as measured, or None where there is nothing to read."""
