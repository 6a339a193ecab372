"""Open loop: streams arrive on a Poisson schedule whatever the engine does.

Arrivals come at ``rate_per_s`` (exponential gaps), each a stream of
``rounds_min``..``rounds_max`` rounds.  Before the schedule starts, a
closed burst of ``1.5 x slots`` streams loads every program the traffic
uses.  The schedule then runs ``warmup_s`` seconds unmeasured; the window
owes an answer for every stream due in the next ``--seconds``.  Arrivals go
on after the window closes until each of those answers is in, or
``drain_s`` seconds have passed.  A stream's latency runs from its due time
to the end of the engine step that set its result; the generator is late
when it submits after the due time, and that wait is the stream's too.

Parameters: ``rate_per_s``, ``rounds_min``, ``rounds_max``, ``warmup_s``,
``drain_s``.
"""

from __future__ import annotations

from harness import arrivals, runner


def drive(run: runner.Run) -> runner.Outcome:
    p = run.traffic
    eng = run.eng
    lengths = arrivals.lengths(run.rng, p["rounds_min"], p["rounds_max"])
    gaps = arrivals.poisson_gaps(run.rng, p["rate_per_s"])

    burst = [run.new_stream(next(lengths), run.now())
             for _ in range(eng.slots * 3 // 2)]
    run.submit(burst)
    run.warm_up(run.step, settled=lambda: all(s.req.done for s in burst))

    run.begin_trace()
    base = run.now()
    t_open = base + p["warmup_s"]
    t_close = t_open + run.seconds
    t_give_up = t_close + p["drain_s"]
    next_due = base + next(gaps)
    owed: list[runner.Stream] = []
    opened = closed = False
    notes: dict = {}
    while True:
        now = run.now()
        if not opened and now >= t_open:
            run.open_window()
            opened = True
            notes["queue_at_open"] = len(eng.queue)
        if opened and not closed and now >= t_close:
            run.close_window(block=False)
            closed = True
            notes["queue_at_close"] = len(eng.queue)
        if closed and (now >= t_give_up or all(s.req.done for s in owed)):
            break
        batch = []
        while next_due <= now:
            s = run.new_stream(next(lengths), next_due)
            batch.append(s)
            if t_open <= next_due < t_close:
                owed.append(s)
            next_due += next(gaps)
        if batch:
            run.submit(batch)
        if eng.queue or any(eng.active):
            run.step()
        else:
            bound = t_open if not opened else t_close if not closed \
                else t_give_up
            run.sleep_until(min(next_due, bound))
    notes["drain_s"] = run.now() - t_close
    run.end_trace()
    return runner.Outcome(
        due=owed, lateness_s=[s.submitted - s.due for s in owed],
        epochs=sum(r.rounds for r in run.window_steps) * run.n, notes=notes)
