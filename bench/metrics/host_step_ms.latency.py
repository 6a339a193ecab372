"""Engine host loop (serve/engine.py step): milliseconds per engine step,
all the time of the harness's span around each eng.step() that began in
the window, over those steps."""


def read(v):
    spans = [(a, b) for a, b in v.run.step_spans if v.run.in_window(a)]
    if not spans:
        return None
    return 1e3 * sum(b - a for a, b in spans) / len(spans)
