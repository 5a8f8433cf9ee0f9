"""Device time of the K-step decode scan executables per token step of
the traced window. The scans are ServeEngine's ``_macro_fn`` jits: the
full variant shows as ``jit__macro_fn``, the ``simple`` one (a jit of a
functools.partial) as ``jit__unknown``, told apart from the map's other
partial jits by running the paged-attention kernel."""

MODULE = r"^jit__(macro_fn|unknown)\("
# the paged-attention Pallas kernel: out, and its (m, l) stats [B, H, 1]
KERNEL = r"= \(bf16\[\d+,\d+,\d+\], f32\[\d+,\d+,1\], f32\[\d+,\d+,1\]\) custom-call$"


def read(run):
    if run.trace is None:
        return None
    steps = run.engine_delta.get("engine.decode_steps", 0)
    t = run.trace.modules_matching(MODULE, containing=KERNEL)
    if steps <= 0 or t <= 0:
        return None
    return t / steps * 1e3
