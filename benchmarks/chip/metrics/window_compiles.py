"""Programs lowered inside the window (count): every jit cache miss,
whether it then compiles or loads from the persistent cache, counted by
a ``jax.monitoring`` listener.  It should read 0; one is a stall of
seconds.  Moves ``itl_p95_ms``."""


def read(run):
    return len(run.compiles_in_window())
