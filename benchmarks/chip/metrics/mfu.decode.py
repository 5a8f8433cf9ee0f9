"""Model FLOPs of every token prefilled and decoded in the traced window
(attention at each token's real context, flops.py) over the window's
seconds times the chip's bf16 peak."""


def read(run):
    if run.trace is None or run.model_flops <= 0:
        return None
    return run.model_flops / (run.window_s * run.peaks["bf16_flops_s"]) * 100
