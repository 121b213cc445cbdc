"""step.mfu (%): the training operations the samples of the window need
(the configuration's ``flops_per_sample``, nothing recomputed counted)
times the window's samples per second, over the chips' bf16 peak
(``peaks.json``)."""


def read(ctx):
    peaks = ctx["peaks"]
    if not peaks:
        return None
    c = ctx["counters"]
    achieved = ctx["flops_per_sample"] * c["samples_per_s"]
    return 100.0 * achieved / (c["chips"] * peaks["bf16_flops_per_s"])
