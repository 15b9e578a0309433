"""Traffic generators, found by the ``generator`` name of a traffic mix's
data file.  Each has ``start_workers(ctx)`` and ``async run(ctx, lineage)``,
which warms up, opens and closes ``ctx.window``, checks the outputs and
returns the run's facts: ``attempted``, ``failed``, ``end_to_end`` and the
counts the per-layer readers divide by."""
