"""Device time of the prefill executables (ServeEngine's ``_prefill_fn``
jit) per thousand prompt tokens prefilled in the traced window."""

MODULE = r"^jit__prefill_fn\("


def read(run):
    if run.trace is None or run.prompt_tokens <= 0:
        return None
    t = run.trace.modules_matching(MODULE)
    return t / run.prompt_tokens * 1e6 if t > 0 else None
