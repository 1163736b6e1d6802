"""Drafted tokens accepted over drafted tokens sent to verify, in percent
(engine counters ``spec_accepted`` / ``spec_drafted``)."""


def read(ctx):
    e = ctx["engine"]
    return 100.0 * e["spec_accepted"] / e["spec_drafted"] if e["spec_drafted"] else None
