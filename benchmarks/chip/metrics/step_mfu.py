"""Model step: FLOPs the forward passes of the traced window need (cold
prefills' prompt tokens and every decode step, counted from shapes) over
the traced window and the chip's bf16 peak, %."""


def read(run):
    tr = run.trace
    if tr is None or run.peak is None or not tr["flops"] \
            or not tr["window_s"]:
        return None
    return 100.0 * tr["flops"] / (tr["window_s"]
                                  * run.peak["bf16_flops_per_s"])
