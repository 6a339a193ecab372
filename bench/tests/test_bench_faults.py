"""A whole run with the timed path broken underneath comes out not correct,
once for each fault a one-chip cell can have: a step that returns its
state unchanged, half of the batch left out (the other half counted
twice), a refresh that keeps the basis it had, and an answer altered where
it is produced.  (No cell spans chips,
so there is no exchange between chips to leave out.)"""

import copy
import dataclasses

import jax
import pytest

from bench_tiny import CELLS, run_tiny, tiny_cell


def _state_unchanged(run):
    from repro.streaming.driver import chunk_stream_step
    cfg = run.eng.cfg

    def body(s, x, rv):
        _, metrics = chunk_stream_step(cfg, s, x, round_valid=rv)
        return s, metrics
    run.eng._step_fn = jax.jit(jax.vmap(body))


def _half_batch(run):
    real = run.eng._step_fn

    def halved(states, batch, rv):
        half = batch.shape[2] // 2
        return real(states, batch.at[:, :, half:].set(batch[:, :, :half]),
                    rv)
    run.eng._step_fn = jax.jit(halved, donate_argnums=(0,))


def _refresh_stale(run):
    """Every refresh keeps the basis it had (no orthogonal iteration)."""
    run.config = copy.deepcopy(run.config)
    run.config["stream"]["refresh_iters"] = 0


def _answer_altered(run):
    real = run.eng._finalize_result

    def altered(slices, reason):
        res = real(slices, reason)
        return dataclasses.replace(res, retained=res.retained + 1e-2)
    run.eng._finalize_result = altered


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch,
                                   _refresh_stale, _answer_altered],
                         ids=lambda f: f.__name__.strip("_"))
@pytest.mark.parametrize("name", CELLS)
def test_fault_is_not_correct(name, fault):
    res = run_tiny(tiny_cell(name), patch=fault)
    assert not res["correct"], res["checks"]
