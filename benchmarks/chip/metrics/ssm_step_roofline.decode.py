"""Roofline share of the Mamba-2 decode state kernel
(``_ssm_step_kernel``, kernels/ssm_step.py) in the decode scans: the
least time the chip needs for the matched calls' work (each lane's f32
state read and written once, its vectors; or the FLOPs, whichever bounds
a call: the family's ``ssm_step_call``) over the calls' device time.
The kernel is a Pallas custom call with no name in the trace: it is the
one that returns the carried state stack f32 [L, B, H, P, N] beside y
f32 [B, H, P]. A family without the function, or a trace without the
kernel, reads nothing."""
import scan_leaves

KERNEL = r"= \(f32\[\d+,(\d+),\d+,\d+,\d+\], f32\[\d+,\d+,\d+\]\) custom-call$"


def read(run):
    cost = getattr(run.cell.family, "ssm_step_call", None)
    if run.trace is None or cost is None:
        return None
    calls = scan_leaves.matching(run.trace, KERNEL)
    t = sum(s for s, _ in calls)
    if t <= 0:
        return None
    p, least = run.peaks, 0.0
    for _, m in calls:
        flops, nbytes = cost(run.cell.dims, int(m.group(1)))
        least += max(nbytes / p["hbm_bytes_s"], flops / p["bf16_flops_s"])
    return least / t * 100
