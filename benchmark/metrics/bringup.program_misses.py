"""Programs that missed the persistent compile cache before the window
opened (jax's own cache events: the program's and the benchmark's alike);
0 from a checkout's second run on."""


def read(ctx):
    return ctx["compiles"].setup_miss
