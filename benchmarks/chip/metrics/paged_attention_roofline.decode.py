"""Roofline share of the paged-attention kernel: the least time the chip
needs for the work of the window's decode tokens (the live KV of each
token's context, or its FLOPs, whichever bounds; flops.paged_attn_run)
over the kernel's device time. The kernel is a Pallas custom call with
no name in the trace: it is the one that returns out [B, H, KV*D] and
its (m, l) stats [B, H, 1]."""

KERNEL = r"= \(bf16\[\d+,\d+,\d+\], f32\[\d+,\d+,1\], f32\[\d+,\d+,1\]\) custom-call$"


def read(run):
    if run.trace is None:
        return None
    t = run.trace.ops_matching(KERNEL)
    if t <= 0 or run.pa_bytes <= 0:
        return None
    p = run.peaks
    least = max(run.pa_bytes / p["hbm_bytes_s"],
                run.pa_flops / p["bf16_flops_s"])
    return least / t * 100
