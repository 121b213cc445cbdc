"""feed.wait_ms (ms per round): the trainer's ``feed_wait_s`` counter (its
wait for the batch builder's next item) over the window, per window round
(``chipbench.scopes.counter_ms``)."""
from chipbench import scopes

COUNTERS = ("feed_wait_s",)


def read(ctx):
    return scopes.counter_ms(ctx["counters"], COUNTERS[0])
