"""pipeline.wait_ms (ms per round): the time the trainer's thread blocked
in the batch builder's ``get`` during the window (a benchmark-side wrapper
around ``data/pipeline.py``), per round."""


def read(ctx):
    return ctx["counters"].get("pipeline_wait_ms")
