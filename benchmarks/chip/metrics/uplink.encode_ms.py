"""uplink.encode_ms (ms per round): device time under the program's
``uplink.encode`` scope in the traced stretch, mean over the cell's chips,
per traced round (``chipbench.scopes.layer_metrics``). Each op goes to the
innermost of the round engine's four scopes on its ``op_name`` path."""
from chipbench import scopes

SCOPES = scopes.SCOPES


def read(ctx):
    return scopes.layer_reading(ctx, "uplink.encode_ms")
