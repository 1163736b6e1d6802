"""Most recurrent-state slots bound to live sequences at any time of the
run, over the slots the cache has (one a lane), in percent: the cache
manager's own count (``serve/kv_cache.py`` ``state_slots_peak``)."""


def read(ctx):
    e = ctx["engine"]
    if not e.get("state_lanes") or e.get("state_slots_peak") is None:
        return None
    return 100.0 * e["state_slots_peak"] / e["state_lanes"]
