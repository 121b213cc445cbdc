"""dispatch.idle_ms (ms per round): device idle time under the host span
``round.dispatch`` in the traced stretch, mean over the cell's chips, per
traced round (``chipbench.scopes.layer_metrics``)."""
from chipbench import scopes

SPANS = ("round.dispatch",)


def read(ctx):
    return scopes.layer_reading(ctx, "dispatch.idle_ms")
