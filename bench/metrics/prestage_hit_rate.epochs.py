"""Host staging and upload: the share of the window's steps that folded
anything whose chunk was staged during the step before (telemetry
``prestaged``), in percent."""


def read(v):
    steps = [r for r in v.run.window_steps if r.live > 0]
    if not steps:
        return None
    return 100.0 * sum(1 for r in steps if r.prestaged) / len(steps)
