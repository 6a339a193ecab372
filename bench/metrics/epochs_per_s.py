"""Epochs folded per second: every epoch the engine folded in the window,
over the whole window (which ends when the device has finished the fleet
state)."""


def read(v):
    window = v.run.window[1] - v.run.window[0]
    return v.outcome.epochs / window if v.outcome.epochs else None
