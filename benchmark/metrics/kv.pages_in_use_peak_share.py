"""Most pages in use after any step of the run, over the usable pages, in
percent (the pool's own count)."""


def read(ctx):
    e = ctx["engine"]
    return 100.0 * e["pages_peak"] / e["usable_pages"]
