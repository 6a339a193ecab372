"""Engine host loop (serve/engine.py step): the engine's telemetry
``retire_s`` (result pulls and StreamResult building) over the window's
steps that folded anything, in milliseconds per step."""


def read(v):
    steps = [r for r in v.run.window_steps if r.live > 0]
    if not steps or not hasattr(steps[0], "retire_s"):
        return None
    return 1e3 * sum(r.retire_s for r in steps) / len(steps)
