"""Requests preempted for pages (``tdx.serve.preempted_requests``)."""


def read(ctx):
    return ctx["engine"]["preemptions"]
