"""Decoding lanes per scan step over the engine's slots, from the
engine's own counts: decode tokens / (token steps x n_slots)."""


def read(run):
    e = run.engine_delta
    steps = e.get("engine.decode_steps", 0)
    if steps <= 0:
        return None
    decoded = e.get("engine.generated", 0) - e.get("engine.prefills", 0)
    return decoded / (steps * int(run.cell.serving["n_slots"])) * 100
