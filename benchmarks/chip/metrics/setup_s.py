"""Process start to the first measured step: loading, weights, compiles
or compile-cache loads, warm-up and the first wave's prefill."""


def read(run):
    return run.setup_s
