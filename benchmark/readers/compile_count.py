"""Backend compiles plus AOT lowers inside the window, from the compile
clock (``jax.monitoring`` events and ``ops.aot`` statistics).  Should be 0:
every shape is warmed during set-up."""


def read(window, facts):
    a, b = window.clock0, window.clock1
    if a is None or b is None:
        return None
    return ((b["backend_compiles"] - a["backend_compiles"])
            + (b["aot_lowers"] - a["aot_lowers"]))
