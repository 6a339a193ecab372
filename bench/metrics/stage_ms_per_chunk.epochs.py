"""Host staging and upload (serve/engine.py _stage/_upload): the engine's
telemetry stage_s over the window's steps that folded anything, in
milliseconds per step (one chunk per slot per step)."""


def read(v):
    steps = [r for r in v.run.window_steps if r.live > 0]
    if not steps:
        return None
    return 1e3 * sum(r.stage_s for r in steps) / len(steps)
