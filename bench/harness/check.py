"""Decide ``correct``: the answers the timed window produced against the
float64 reference (``reference.py``), each number beside its limit.

Numbers, each the worst over the streams compared (``refresh_shortfall``:
the mean):

``missing``         streams due in the window with no completed result.
``books``           streams whose result misstates what was folded: rounds
                    not R, or a refresh count that the schedule forbids
                    (none before ``warmup_rounds``, at least one after,
                    at most one per chunk decision past warmup).
``fold_trace_rel``  |total_variance - trace(C)| / trace(C): the fold.
``fold_energy_rel`` max_k |energies_k - (W'CW)_kk| / trace(C): the fold
                    along the basis the stream returned.
``retained_abs``    |retained - trace(W'CW)/trace(C)|: the summary.
``refresh_shortfall`` (sum of the top-q eigenvalues of C_r - trace(W'C_rW))
                    / trace(C_r), where C_r is the reference's covariance
                    at the decision whose refresh set the returned basis:
                    the refresh captures the top-q variance.  Read on the
                    streams where the schedule places that decision: one
                    refresh (the unconditional first, at the first decision
                    past warmup), or a refresh at every decision (the last).
                    The mean, not the worst: ``refresh_iters`` orthogonal
                    iterations from a random start leave a stream whose
                    q-th and (q+1)-th eigenvalues lie close some way off
                    the top-q optimum by design.
``eps_excess``      worst compression error minus fp32(epsilon): the
                    compression stage's guarantee (limit 0).

C is the reference's banded covariance of the stream's own input (C_r: of
its rounds up to the refresh); W, and every other value compared, is what
``StreamResult`` handed back.
"""

from __future__ import annotations

import math

import numpy as np

from harness import reference


def chunk_of(config: dict) -> int:
    """Rounds per scheduler decision: the engine's chunk, or the fleet's."""
    return (config.get("engine") or config["fleet"])["chunk"]


def _decision_ends(rounds: int, chunk: int, warmup: int) -> list[int]:
    """Round indices of the scheduler decisions past warmup (one per
    chunk, at its last folded round)."""
    ends = [min(chunk * k, rounds) - 1
            for k in range(1, -(-rounds // chunk) + 1)]
    return [e for e in ends if e >= warmup]


def _decisions_past_warmup(rounds: int, chunk: int, warmup: int) -> int:
    return len(_decision_ends(rounds, chunk, warmup))


def refresh_round(rounds: int, refreshes: int, chunk: int,
                  warmup: int) -> int | None:
    """The round index of the decision whose refresh set the returned
    basis, where the schedule alone places it, else None."""
    ends = _decision_ends(rounds, chunk, warmup)
    if refreshes == 1 and ends:
        return ends[0]                  # the first refresh is unconditional
    if refreshes >= 1 and refreshes == len(ends):
        return ends[-1]
    return None


def stream_numbers(rounds: np.ndarray, result, stream_cfg: dict,
                   chunk: int) -> dict:
    """The reference numbers of one completed stream."""
    q, beta, h = stream_cfg["q"], stream_cfg["forgetting"], \
        stream_cfg["halfwidth"]
    c = reference.banded_covariance(rounds, beta, h)
    tr = float(np.trace(c))
    w = np.asarray(result.components, np.float64)
    energies_ref = np.sum(w * (c @ w), axis=0)
    out = {
        "fold_trace_rel": abs(result.total_variance - tr) / tr,
        "fold_energy_rel": float(np.max(np.abs(
            np.asarray(result.energies, np.float64) - energies_ref))) / tr,
        "retained_abs": float(abs(result.retained
                                  - energies_ref.sum() / tr)),
    }
    r = refresh_round(len(rounds), result.refreshes, chunk,
                      stream_cfg["warmup_rounds"])
    if r is not None:
        cr = c if r == len(rounds) - 1 else \
            reference.banded_covariance(rounds[:r + 1], beta, h)
        trr = float(np.trace(cr))
        out["refresh_shortfall"] = float(
            reference.top_eigvals(cr, q).sum() - np.sum(w * (cr @ w))) / trr
    return out


def books_ok(rounds: int, result, chunk: int, warmup: int) -> bool:
    allowed = _decisions_past_warmup(rounds, chunk, warmup)
    return (result.reason == "completed" and result.rounds == rounds
            and (result.refreshes >= 1) == (allowed >= 1)
            and result.refreshes <= allowed)


def evaluate(streams: list, config: dict, params: dict,
             rng: np.random.Generator) -> dict:
    """Every number of the module docstring for ``streams``, the
    ``Stream`` records the window owed an answer.  ``params`` is the cell's
    ``check`` group: ``sample``, the most streams compared with the
    reference.  Returns name -> value."""
    scfg = config["stream"]
    chunk = chunk_of(config)
    done = [s for s in streams if s.req.done]
    nums: dict = {"missing": float(len(streams) - sum(
        1 for s in done if s.req.result.reason == "completed"))}
    nums["books"] = float(sum(
        0 if books_ok(s.rounds, s.req.result, chunk, scfg["warmup_rounds"])
        else 1 for s in done))
    comp = scfg.get("compression")
    if comp is not None and done:
        worst = -math.inf
        for s in done:
            worst = _worst(worst, s.req.result.compression_max_err)
        nums["eps_excess"] = worst - float(np.float32(comp["epsilon"]))
    sample = done
    if len(done) > params["sample"]:
        # a seeded sample that always holds the longest stream
        longest = max(range(len(done)), key=lambda i: done[i].rounds)
        rest = [i for i in range(len(done)) if i != longest]
        pick = rng.choice(rest, size=params["sample"] - 1, replace=False)
        sample = [done[longest]] + [done[i] for i in sorted(pick)]
    shortfalls = []
    for s in sample:
        for k, v in stream_numbers(s.req.rounds, s.req.result, scfg,
                                   chunk).items():
            if k == "refresh_shortfall":
                shortfalls.append(_worst(-math.inf, v))
            else:
                nums[k] = _worst(nums.get(k, -math.inf), v)
    if shortfalls:
        nums["refresh_shortfall"] = float(np.mean(shortfalls))
    return nums


def _worst(a: float, b: float) -> float:
    """The larger of two readings; a NaN reads as infinitely wrong."""
    return max(a, b if not math.isnan(b) else math.inf)


def verdict(nums: dict, limits: dict) -> tuple[bool, dict]:
    """``(correct, {name: {"value", "limit"}})`` over the limited numbers.
    A limited number that could not be read (no stream to read it from)
    fails."""
    table = {}
    ok = bool(limits)
    for name, limit in limits.items():
        value = nums.get(name)
        table[name] = {"value": value, "limit": limit}
        ok &= value is not None and value <= limit
    return ok, table
