"""dispatch.host_ms (ms per round): the trainer's ``dispatch_s`` counter
(host time inside the executable calls) over the window, per window round
(``chipbench.scopes.counter_ms``)."""
from chipbench import scopes

COUNTERS = ("dispatch_s",)


def read(ctx):
    return scopes.counter_ms(ctx["counters"], COUNTERS[0])
