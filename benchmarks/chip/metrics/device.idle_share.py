"""device.idle_share (%): 1 minus the union of device-operation intervals
over the traced window, averaged over the chips (``chipbench.trace``)."""


def read(ctx):
    t = ctx["trace"]
    if t is None:
        return None
    busy = [d["busy_ns"] for d in t["devices"].values()]
    return 100.0 * (1.0 - sum(busy) / len(busy) / t["window_ns"])
