"""Program-contract subsystem tests (DESIGN.md Sec. 15).

Four layers:

* the jaxpr walker itself (recursion, loop weighting, collective counts);
* the contract registry: the required ids exist and every registered
  contract passes against the live repo;
* break-detection: deliberately violating an invariant (a second pallas
  launch, an extra cross-host psum, a dropped donation) FAILS with a
  report naming the violated contract/rule — the property that makes the
  checker worth wiring into CI;
* booked == counted for the scheduler's per-round/per-refresh bill against
  :func:`repro.core.costs.lossy_round_cost` /
  :func:`repro.core.costs.lossy_refresh_cost` (the cost pair the repolint
  ``unreferenced-cost-helper`` rule flagged as unpinned).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import contracts
from repro.analysis.jaxpr_lint import (CollectiveBudget, ForbidInLoops,
                                       Fp32Accumulators, NoF64,
                                       PrimitiveBudget, collective_counts,
                                       count_primitive, count_primitives,
                                       iter_eqns)
from repro.core import costs
from repro.streaming.driver import (StreamConfig, chunk_stream_step,
                                    stream_init, stream_run)

REQUIRED_CONTRACTS = (
    "chunk.body", "chunk.body.split", "chunk.fused.fp32", "chunk.fused.bf16",
    "driver.hot-loop", "dtype.policy", "hierarchy.refresh", "engine.step",
    "engine.step.pipelined",
)


# ===========================================================================
# The walker
# ===========================================================================
class TestWalker:
    def test_counts_inside_cond_branches(self):
        def f(x):
            return jax.lax.cond(x.sum() > 0,
                                lambda v: jnp.sin(v),
                                lambda v: jnp.sin(jnp.sin(v)), x)

        jx = jax.make_jaxpr(f)(jnp.ones(3))
        # both branches count (repo convention for launch budgets)
        assert count_primitive(jx, "sin") == 3

    def test_loop_weighted_scan_multiplies_length(self):
        def f(x):
            return jax.lax.scan(lambda c, _: (jnp.sin(c), None), x,
                                None, length=5)[0]

        jx = jax.make_jaxpr(f)(jnp.ones(3))
        assert count_primitive(jx, "sin") == 1
        assert count_primitive(jx, "sin", loop_weighted=True) == 5

    def test_loop_weighted_fori_and_nesting(self):
        def f(x):
            def body(_, c):
                return jax.lax.scan(lambda a, __: (jnp.sin(a), None), c,
                                    None, length=3)[0]
            return jax.lax.fori_loop(0, 4, body, x)

        jx = jax.make_jaxpr(f)(jnp.ones(3))
        assert count_primitive(jx, "sin", loop_weighted=True) == 12

    def test_while_loop_trip_from_cond_literal(self):
        def f(x):
            return jax.lax.while_loop(lambda c: c[0] < 7,
                                      lambda c: (c[0] + 1, jnp.sin(c[1])),
                                      (jnp.int32(0), x))[1]

        jx = jax.make_jaxpr(f)(jnp.ones(3))
        assert count_primitive(jx, "sin", loop_weighted=True) == 7

    def test_count_primitives_matches_single_counts(self):
        cfg = StreamConfig(p=12, q=3, halfwidth=2, warmup_rounds=4)
        st = stream_init(cfg, jax.random.PRNGKey(0))
        jx = jax.make_jaxpr(
            lambda s, x: chunk_stream_step(cfg, s, x))(
            st, jnp.zeros((4, 4, 12), jnp.float32))
        many = count_primitives(jx, {"pallas_call", "eigh"})
        assert many["pallas_call"] == count_primitive(jx, "pallas_call") == 1
        assert many["eigh"] == count_primitive(jx, "eigh") == 1

    def test_collective_counts_through_shard_map(self):
        # the shard_map param is a RAW Jaxpr (no ClosedJaxpr wrapper) —
        # exactly the case the old ad-hoc test helpers failed to descend
        from jax.sharding import Mesh, PartitionSpec as P

        mesh = Mesh(np.array(jax.devices()[:1]), ("r",))
        f = jax.shard_map(lambda x: jax.lax.psum(jnp.sum(x), "r"),
                          mesh=mesh, in_specs=P("r"), out_specs=P(),
                          check_vma=False)
        jx = jax.make_jaxpr(f)(jnp.ones((1, 3)))
        assert collective_counts(jx) == {"r": {"psum": 1}}


# ===========================================================================
# The registry against the live repo
# ===========================================================================
class TestRegisteredContracts:
    def test_required_contracts_registered(self):
        reg = contracts.load_entry_points()
        missing = [cid for cid in REQUIRED_CONTRACTS if cid not in reg]
        assert not missing, f"unregistered contracts: {missing}"
        assert len(reg) >= 6

    @pytest.mark.parametrize("cid", REQUIRED_CONTRACTS)
    def test_contract_passes_on_repo(self, cid):
        contracts.load_entry_points()
        results = contracts.check_contract(contracts.get_contract(cid))
        assert results, f"{cid} produced no rule results"
        bad = [r.line() for r in results if not r.ok]
        assert not bad, "\n".join(bad)

    def test_engine_step_refresh_is_bucketed(self):
        """The engine step's refresh runs inside the bucket loop on B slots:
        its ``eigh`` is batched over B = bucket_size(S), not over the S
        slots a vmapped select would refresh."""
        from repro.streaming.driver import engine_chunk_step_fn
        from repro.streaming.scheduler import bucket_size
        slots = 16
        cfg = StreamConfig(p=8, q=2, halfwidth=1, warmup_rounds=2,
                           interpret=True)
        states = jax.vmap(lambda k: stream_init(cfg, k))(
            jax.random.split(jax.random.PRNGKey(0), slots))
        jx = jax.make_jaxpr(engine_chunk_step_fn(cfg))(
            states, jnp.zeros((slots, 2, 4, 8), jnp.float32),
            jnp.ones((slots, 2), jnp.float32))
        sites = [site for site in iter_eqns(jx) if site.name == "eigh"]
        assert len(sites) == 1
        (site,) = sites
        assert bucket_size(slots) == 8
        assert site.eqn.invars[0].aval.shape == (8, 2, 2)
        assert "while_body" in site.path

    def test_batched_chunk_refresh_is_bucketed(self):
        """The batch drivers' chunk path refreshes inside the same bucket
        loop, once per decision step of its scan: its ``eigh`` is batched
        over B = bucket_size(S) in a ``while`` body, not over the S
        networks a vmapped select would refresh."""
        from repro.streaming.driver import batched_stream_run
        from repro.streaming.scheduler import bucket_size
        networks = 16
        cfg = StreamConfig(p=8, q=2, halfwidth=1, warmup_rounds=2,
                           interpret=True)
        states = jax.vmap(lambda k: stream_init(cfg, k))(
            jax.random.split(jax.random.PRNGKey(0), networks))
        jx = jax.make_jaxpr(
            lambda s, x: batched_stream_run(cfg, s, x, chunk=2))(
            states, jnp.zeros((networks, 6, 4, 8), jnp.float32))
        sites = [site for site in iter_eqns(jx) if site.name == "eigh"]
        assert len(sites) == 1
        (site,) = sites
        assert bucket_size(networks) == 8
        assert site.eqn.invars[0].aval.shape == (8, 2, 2)
        assert "while_body" in site.path
        assert site.path.index("scan") < site.path.index("while_body")

    @pytest.mark.parametrize("driver", ["batched", "hierarchical"])
    def test_default_fleet_refresh_is_bucketed(self, driver):
        """Called without ``chunk``, the batched and two-level drivers
        decide every round through the same bucket loop: one ``eigh``,
        batched over B = bucket_size(S) in a ``while`` body inside the
        scan over rounds, not over the S networks a vmapped
        ``stream_run`` would refresh with a select."""
        from repro.launch.mesh import make_fleet_mesh
        from repro.streaming.driver import batched_stream_run
        from repro.streaming.hierarchy import hierarchical_stream_run
        from repro.streaming.scheduler import bucket_size
        networks = 16
        cfg = StreamConfig(p=8, q=2, halfwidth=1, warmup_rounds=2,
                           interpret=True)
        states = jax.vmap(lambda k: stream_init(cfg, k))(
            jax.random.split(jax.random.PRNGKey(0), networks))
        mesh = make_fleet_mesh(region=1, data=1)

        def run(s, x):
            if driver == "batched":
                return batched_stream_run(cfg, s, x)
            return hierarchical_stream_run(cfg, mesh, s, x)

        jx = jax.make_jaxpr(run)(
            states, jnp.zeros((networks, 6, 4, 8), jnp.float32))
        sites = [site for site in iter_eqns(jx) if site.name == "eigh"]
        assert len(sites) == 1
        (site,) = sites
        assert bucket_size(networks) == 8
        assert site.eqn.invars[0].aval.shape == (8, 2, 2)
        assert "while_body" in site.path
        assert site.path.index("scan") < site.path.index("while_body")

    def test_hierarchy_refresh_collective_budget(self):
        """Exactly one all_gather + two psums (trace partial and refresh
        flags: JAX binds a psum of a tuple leaf by leaf) on the 'region'
        axis per hierarchical refresh/merge — asserted on the raw counts,
        independently of the CollectiveBudget rule implementation."""
        contracts.load_entry_points()
        c = contracts.get_contract("hierarchy.refresh")
        (label, jx), = c.trace().items()
        counts = collective_counts(jx)
        assert set(counts) == {"region"}, (label, counts)
        assert counts["region"] == {"all_gather": 1, "psum": 2}


# ===========================================================================
# Break-detection: violated invariants FAIL with a named report
# ===========================================================================
class TestBreakDetection:
    def _chunk_jaxpr(self, wrap=None):
        cfg = StreamConfig(p=12, q=3, halfwidth=2, warmup_rounds=4)
        st = stream_init(cfg, jax.random.PRNGKey(0))
        step = (lambda s, x: chunk_stream_step(cfg, s, x))
        fn = wrap(step) if wrap is not None else step
        return jax.make_jaxpr(fn)(st, jnp.zeros((4, 4, 12), jnp.float32))

    def test_second_pallas_call_fails_budget(self):
        def twice(step):
            def f(s, x):
                s1, m = step(s, x)
                return step(s1, x)[0], m          # a second launch
            return f

        rep = PrimitiveBudget("pallas_call", exact=1).check(
            self._chunk_jaxpr(twice))
        assert not rep.ok
        assert rep.rule == "budget:pallas_call"
        assert "2" in rep.detail and "1" in rep.detail

    def test_extra_psum_fails_collective_budget(self):
        from jax.sharding import Mesh, PartitionSpec as P

        mesh = Mesh(np.array(jax.devices()[:1]), ("region",))

        def merge(x):
            g = jax.lax.all_gather(x, "region", tiled=True)
            tot = jax.lax.psum(jnp.sum(x), "region")
            extra = jax.lax.psum(jnp.max(x), "region")   # the violation
            return jnp.sum(g) + tot + extra

        f = jax.shard_map(merge, mesh=mesh, in_specs=P("region"),
                          out_specs=P(), check_vma=False)
        jx = jax.make_jaxpr(f)(jnp.ones((1, 3)))
        rule = CollectiveBudget(axis="region",
                                budgets=(("all_gather", 1), ("psum", 1)))
        rep = rule.check(jx)
        assert not rep.ok
        assert rep.rule == "collectives:region"
        assert "psum" in rep.detail

    def test_host_callback_in_loop_fails(self):
        def f(x):
            def body(c, _):
                jax.debug.callback(lambda v: None, c.sum())
                return jnp.sin(c), None
            return jax.lax.scan(body, x, None, length=3)[0]

        jx = jax.make_jaxpr(f)(jnp.ones(3))
        rep = ForbidInLoops().check(jx)
        assert not rep.ok and "debug_callback" in rep.detail

    def test_f64_fails_dtype_rule(self):
        with jax.enable_x64(True):
            jx = jax.make_jaxpr(
                lambda x: jnp.sum(x.astype(jnp.float64)))(jnp.ones(3))
        rep = NoF64().check(jx)
        assert not rep.ok

    def test_bf16_scan_carry_fails_fp32_accumulators(self):
        def f(x):
            return jax.lax.scan(lambda c, _: (c * jnp.bfloat16(0.5), None),
                                x.astype(jnp.bfloat16), None, length=3)[0]

        rep = Fp32Accumulators().check(jax.make_jaxpr(f)(jnp.ones(3)))
        assert not rep.ok and "bfloat16" in rep.detail

    def test_check_contract_reports_broken_trace_as_failure(self):
        broken = contracts.Contract(
            id="x.broken", where="nowhere", claim="trace crashes",
            trace=lambda: (_ for _ in ()).throw(RuntimeError("gone")),
            rules=(NoF64(),))
        results = contracts.check_contract(broken)
        assert len(results) == 1
        assert not results[0].ok and results[0].rule == "trace"

    def test_dropped_donation_fails_runtime_check(self):
        cfg = StreamConfig(p=8, q=2, halfwidth=1, warmup_rounds=2)
        st = stream_init(cfg, jax.random.PRNGKey(0))
        x = jnp.zeros((2, 4, 8), jnp.float32)
        donated = jax.jit(lambda s, xc: chunk_stream_step(cfg, s, xc),
                          donate_argnums=(0,))
        plain = jax.jit(lambda s, xc: chunk_stream_step(cfg, s, xc))
        assert contracts.donation_report(donated, st, x, argnum=0).ok
        rep = contracts.donation_report(plain, st, x, argnum=0)
        assert not rep.ok and "donate" in rep.detail

    def test_retrace_report_counts_cache_entries(self):
        f = jax.jit(lambda x: x + 1)
        for _ in range(3):
            f(jnp.ones(4)).block_until_ready()
        assert contracts.retrace_report(f, 3).ok


# ===========================================================================
# Booked == counted: the scheduler's bill against the cost-model helpers
# ===========================================================================
class TestSchedulerBillMatchesCostModel:
    @pytest.mark.parametrize("link_loss", [0.0, 0.1])
    def test_comm_packets_equals_rounds_plus_refreshes(self, link_loss):
        cfg = StreamConfig(p=12, q=3, halfwidth=2, forgetting=0.95,
                           drift_threshold=0.05, warmup_rounds=4,
                           link_loss=link_loss, interpret=True)
        rng = np.random.default_rng(0)
        R = 16
        xs = jnp.asarray(rng.normal(size=(R, 6, cfg.p)).astype(np.float32))
        fin, _ = stream_run(cfg, stream_init(cfg, jax.random.PRNGKey(1)), xs)

        per_round = costs.lossy_round_cost(
            cfg.n_max, cfg.q, cfg.c_max, cfg.link_loss,
            cfg.max_retries).communication
        per_refresh = costs.lossy_refresh_cost(
            cfg.p, cfg.q, cfg.n_max, cfg.c_max, cfg.refresh_iters,
            cfg.link_loss, cfg.max_retries).communication
        refreshes = int(fin.sched.refreshes)
        assert refreshes >= 1                    # warmup refresh fired
        expected = R * per_round + refreshes * per_refresh
        np.testing.assert_allclose(float(fin.sched.comm_packets), expected,
                                   rtol=1e-5)
