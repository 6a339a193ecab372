"""Drift probe and refresh (streaming/scheduler.py): refreshes over
scheduler decisions, in percent, for the streams the window owed (one
decision per chunk a stream folds).  Under vmap every slot pays the refresh
at every decision, so this is the share of that work that was used."""


from harness import check


def read(v):
    chunk = check.chunk_of(v.run.config)
    done = [s for s in v.outcome.due if s.req.done]
    decisions = sum(-(-s.rounds // chunk) for s in done)
    if not decisions:
        return None
    return 100.0 * sum(s.req.result.refreshes for s in done) / decisions
