"""mesh.collective_exposed_ms (ms per round): the time of collective
instructions (all-reduce and kin) in the trace during which no other
instruction runs on that chip, averaged over the chips, per traced round.
Nothing on one chip."""


def read(ctx):
    t, c = ctx["trace"], ctx["counters"]
    if t is None or c["chips"] < 2:
        return None
    per = [d["exposed_collective_ns"] for d in t["devices"].values()]
    return sum(per) / len(per) / 1e6 / c["traced_rounds"]
