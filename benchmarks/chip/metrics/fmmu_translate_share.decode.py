"""Device time of the FMMU translate kernel over device busy time. The
kernel is a Pallas custom call with no name in the trace: it is the one
that returns four int32 lane columns [N, 1] and the ref bits [S, W]."""

KERNEL = r"= \((s32\[\d+,1\], ){4}s32\[\d+,\d+\]\) custom-call$"


def read(run):
    if run.trace is None or run.trace.busy_s <= 0:
        return None
    t = run.trace.ops_matching(KERNEL)
    return t / run.trace.busy_s * 100 if t > 0 else None
