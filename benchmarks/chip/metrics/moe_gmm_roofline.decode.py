"""Roofline share of the expert grouped matmuls (megablox ``gmm``,
ops.grouped_matmul) in the decode scans: the least time the chip needs
for the matched calls' work (the held experts' weights read once and
the routed rows' activations, or the routed FLOPs at the chip's expected
hits per token, whichever bounds a call: the family's ``moe_gmm_call``)
over the calls' device time. The kernel is a Pallas custom call with no
name in the trace: in the scans it is the one custom call with a single
bf16 [rows, n] result (the gate and up projections, n = d_ff, and the
down projection, n = d_model). A family without the function, or a
trace without the kernel, reads nothing."""
import scan_leaves

KERNEL = r"= bf16\[(\d+),(\d+)\] custom-call$"


def read(run):
    cost = getattr(run.cell.family, "moe_gmm_call", None)
    if run.trace is None or cost is None:
        return None
    calls = scan_leaves.matching(run.trace, KERNEL)
    t = sum(s for s, _ in calls)
    if t <= 0:
        return None
    p, least = run.peaks, 0.0
    for _, m in calls:
        flops, nbytes = cost(run.cell.dims, int(m.group(1)), int(m.group(2)))
        least += max(nbytes / p["hbm_bytes_s"], flops / p["bf16_flops_s"])
    return least / t * 100
