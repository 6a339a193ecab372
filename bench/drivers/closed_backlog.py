"""Closed backlog: the queue never runs dry, so the chip sets the pace.

Before every engine step the admission queue is topped up to at least
``backlog`` waiting streams, of ``rounds_min``..``rounds_max`` rounds.  The
window runs for ``--seconds`` and ends when the device has finished the
fleet state.  The answers owed are those of the streams completed inside
the window.

Parameters: ``backlog``, ``rounds_min``, ``rounds_max``,
``warmup_steps_min`` (steps before the window, past the compiles, so the
lengths of the streams in flight have mixed).
"""

from __future__ import annotations

from harness import arrivals, runner


def drive(run: runner.Run) -> runner.Outcome:
    p = run.traffic
    eng = run.eng
    lengths = arrivals.lengths(run.rng, p["rounds_min"], p["rounds_max"])

    def one_step():
        need = p["backlog"] - len(eng.queue)
        if need > 0:
            now = run.now()
            run.submit([run.new_stream(next(lengths), now)
                        for _ in range(need)])
        run.step()

    run.warm_up(one_step, settled=lambda: (
        len(eng.retired_log) > 0
        and len(run.step_spans) >= p["warmup_steps_min"]))
    run.begin_trace()
    t0 = run.open_window()
    while run.now() < t0 + run.seconds:
        one_step()
    run.close_window(block=True)
    run.end_trace()
    done = [s for s in run.streams if run.in_window(s.done)]
    return runner.Outcome(
        due=done, lateness_s=[s.submitted - s.due for s in done],
        epochs=sum(r.rounds for r in run.window_steps) * run.n)
