"""Device time a step of the routed experts' dispatch: the ops under the
program's ``moe_dispatch`` scope (router, top-k, sort by expert, permute,
un-permute and weighted combine), forward and backward
(``moe_trace.scope_ms``)."""

from chip import moe_trace


def read(run):
    return moe_trace.scope_ms(run, "moe_dispatch")
