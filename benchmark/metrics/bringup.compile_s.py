"""Seconds the backend spent compiling before the window opened: jax's
own backend-compile durations as the program's compile log keeps them
(``observe.compilelog``), the program's and the benchmark's alike; a
program loaded from the persistent cache counts nothing here.  Near 0 from
a checkout's second run on."""
from benchmark import spanlog


def read(ctx):
    return spanlog.setup_seconds(ctx, ("backend_compile",))
