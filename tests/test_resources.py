"""Static resource certifier (repro.analysis.resources; DESIGN.md Sec. 16).

Three layers under test:

* derivation — VMEM/HBM/flop bills read off traced ``pallas_call`` params
  (fetch-on-change HBM semantics, dtype-aware byte accounting), collective
  payloads read off merge collectives;
* budgets — adversarial fixtures that MUST fail: an oversized BlockSpec
  (``budget:vmem``), an operand re-streamed across the grid
  (``budget:hbm``), a padded merge record (``wire:region``);
* reconciliation — booked == traced against ``costs.merge_record_elems``
  and ``ops.kernel_block_plan``, and the committed baseline round-trip.

Everything traces only (``jax.make_jaxpr``); nothing executes or compiles.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.experimental import pallas as pl

from repro.analysis import resources as R
from repro.analysis.jaxpr_lint import (PrimitiveBudget, UnknownTripError,
                                       count_primitive, while_trip_count)
from repro.core import costs
from repro.kernels import ops


def _copy_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...] * 2.0


def _trace_copy(shape=(8, 8), block=None):
    """One well-behaved pallas_call: every block fetched exactly once."""
    block = block or shape

    def fn(x):
        grid = (shape[0] // block[0],)
        return pl.pallas_call(
            _copy_kernel,
            grid=grid,
            in_specs=[pl.BlockSpec(block, lambda i: (i, 0))],
            out_specs=pl.BlockSpec(block, lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct(shape, jnp.float32),
        )(x)

    return jax.make_jaxpr(fn)(jnp.zeros(shape, jnp.float32))


def _trace_restream():
    """Adversarial: the input block index ignores the slow grid axis's
    progress and cycles, so the operand is re-streamed from HBM once per
    outer step — the exact extra-round-trip pattern budget:hbm exists to
    catch.  x (2, 8) is read twice (4 fetches of 2 blocks)."""

    def fn(x):
        return pl.pallas_call(
            _copy_kernel,
            grid=(2, 2),
            in_specs=[pl.BlockSpec((1, 8), lambda i, j: (j, 0))],
            out_specs=pl.BlockSpec((1, 8), lambda i, j: (i * 2 + j, 0)),
            out_shape=jax.ShapeDtypeStruct((4, 8), jnp.float32),
        )(x)

    return jax.make_jaxpr(fn)(jnp.zeros((2, 8), jnp.float32))


def _trace_oversized():
    """Adversarial: a (4096, 1024) fp32 block = 16MiB per operand; with
    in + out double-buffered that is 64MiB of VMEM against the 16MiB
    budget."""

    def fn(x):
        return pl.pallas_call(
            _copy_kernel,
            grid=(1,),
            in_specs=[pl.BlockSpec((4096, 1024), lambda i: (0, 0))],
            out_specs=pl.BlockSpec((4096, 1024), lambda i: (0, 0)),
            out_shape=jax.ShapeDtypeStruct((4096, 1024), jnp.float32),
        )(x)

    return jax.make_jaxpr(fn)(jnp.zeros((4096, 1024), jnp.float32))


def _trace_merge(q_gathered: int):
    """The hierarchy merge shape in miniature: ONE tiled all_gather of a
    (1, q) energy record + ONE psum of the scalar trace partial, on the
    'region' mesh axis."""
    from jax.sharding import PartitionSpec as P

    mesh = jax.make_mesh((1,), ("region",))

    def local(lam, den):
        table = jax.lax.all_gather(lam, "region", tiled=True)
        total = jax.lax.psum(jnp.sum(den), "region")
        return table, total

    fn = jax.shard_map(local, mesh=mesh,
                       in_specs=(P("region"), P("region")),
                       out_specs=(P(), P()), check_vma=False)
    return jax.make_jaxpr(fn)(jnp.zeros((1, q_gathered), jnp.float32),
                              jnp.zeros((1,), jnp.float32))


def _trace_fused(precision: str, rows=32, p=16, q=2):
    x = jnp.zeros((rows, p), jnp.float32)
    w = jnp.ones((rows,), jnp.float32)
    basis = jnp.zeros((p, q), jnp.float32)

    def fn(x, w, b):
        return ops.fused_stream_update(
            x, w, b, halfwidth=1, with_compress=False, with_monitor=True,
            precision=precision, interpret=True)

    return jax.make_jaxpr(fn)(x, w, basis)


class TestDerivation:
    def test_single_pass_copy_bill(self):
        kernels = R.pallas_resources(_trace_copy(shape=(8, 8), block=(2, 8)))
        assert len(kernels) == 1
        k = kernels[0]
        assert k.grid == (4,)
        nbytes = 8 * 8 * 4
        assert k.hbm_read_bytes == nbytes          # each block once
        assert k.hbm_write_bytes == nbytes
        assert k.vmem_bytes == 2 * 2 * (2 * 8 * 4)  # in+out, double-buffered
        # flop model: one mul per output element per grid cell
        assert k.flops == 8 * 8
        assert all(o.exact for o in k.inputs + k.outputs)

    def test_entry_aggregation_and_passes(self):
        entry = R.entry_resources(_trace_copy(shape=(8, 8), block=(2, 8)))
        assert entry.launches == 1
        assert entry.hbm_passes == pytest.approx(1.0)
        assert entry.intensity == pytest.approx(
            entry.flops / (entry.hbm_read_bytes + entry.hbm_write_bytes))
        q = entry.quantities()
        assert q["hbm_passes"] == pytest.approx(1.0)
        assert q["launches"] == 1

    def test_restream_counts_extra_fetches(self):
        k = R.pallas_resources(_trace_restream())[0]
        (xin,) = k.inputs
        assert xin.fetches == 4                    # 2 blocks x 2 sweeps
        assert xin.passes == pytest.approx(2.0)
        (out,) = k.outputs
        assert out.passes == pytest.approx(1.0)

    def test_merge_collective_payload(self):
        (coll,) = [c for c in R.collective_resources(_trace_merge(2))
                   if c.primitive == "all_gather"]
        assert coll.axes == ("region",)
        assert coll.record_elems == 2
        assert coll.payload_bytes == 2 * 4
        (red,) = [c for c in R.collective_resources(_trace_merge(2))
                  if c.primitive == "psum"]
        assert red.scalar_operands == 1


class TestDtypeAccounting:
    """bf16 fused path: tile loads halve, accumulators stay fp32 — the
    byte bill must keep the two populations separate."""

    def test_bf16_tiles_half_fp32_accumulators_full(self):
        (kf,) = R.pallas_resources(_trace_fused("fp32"))
        (kb,) = R.pallas_resources(_trace_fused("bf16"))
        by_dtype = kb.bytes_by_dtype()
        assert by_dtype.get("bfloat16", 0) > 0
        assert by_dtype.get("float32", 0) > 0
        # every downcast tile operand moves exactly half its fp32 bytes
        fp32_in = {o.origin: o for o in kf.inputs}
        tiles = [o for o in kb.inputs if o.dtype == "bfloat16"]
        assert tiles, "bf16 trace has no bf16 tile operands"
        for o in tiles:
            assert 2 * o.fetched_bytes == fp32_in[o.origin].fetched_bytes
        # outputs (band accumulator, z, t2/spe) all fp32 in BOTH traces
        assert all(o.dtype == "float32" for o in kb.outputs)
        assert kb.hbm_read_bytes < kf.hbm_read_bytes
        assert kb.hbm_write_bytes == kf.hbm_write_bytes

    def test_block_plan_is_the_traced_grid(self):
        """booked == traced for tiling: the plan the wrapper picks is the
        grid the pallas_call was traced with."""
        plan = ops.kernel_block_plan("fused", rows=32, p=16)
        (k,) = R.pallas_resources(_trace_fused("fp32"))
        assert k.grid == plan["grid"]
        assert plan["grid"] == (plan["feature_blocks"], plan["row_blocks"])


class TestBudgets:
    def test_oversized_blockspec_fails_vmem(self):
        rep = R.VmemBudget().check(_trace_oversized())
        assert rep.rule == "budget:vmem"
        assert not rep.ok
        assert "VMEM" in rep.detail and ">" in rep.detail

    def test_vmem_passes_and_reports_headroom(self):
        rep = R.VmemBudget().check(_trace_copy())
        assert rep.ok
        assert "%" in rep.detail and "double-buffered" in rep.detail

    def test_vmem_requires_a_kernel(self):
        jx = jax.make_jaxpr(lambda x: x + 1.0)(jnp.zeros((4,)))
        assert not R.VmemBudget().check(jx).ok

    def test_restream_fails_hbm_budget(self):
        rep = R.HbmTrafficBudget(max_passes=1.0).check(_trace_restream())
        assert rep.rule == "budget:hbm"
        assert not rep.ok
        assert "passes" in rep.detail

    def test_single_pass_origin_pin(self):
        # generous pass cap, but the named operand must be one tile-load
        rep = R.HbmTrafficBudget(max_passes=3.0,
                                 single_pass=("x_ref",)
                                 ).check(_trace_restream())
        assert not rep.ok
        assert "x_ref" in rep.detail
        assert R.HbmTrafficBudget(max_passes=1.0,
                                  single_pass=("x_ref",)
                                  ).check(_trace_copy()).ok

    def test_padded_merge_record_fails_wire_budget(self):
        booked = costs.merge_record_elems(2)       # q energies + trace
        good = R.WireBytesBudget(axis="region", record_elems=booked)
        assert good.check(_trace_merge(2)).ok      # 2 gathered + 1 scalar
        bad = good.check(_trace_merge(4))          # padded to 4 energies
        assert not bad.ok
        assert f"booked {booked}" in bad.detail
        assert good.name == "wire:region"

    def test_wire_budget_requires_collectives(self):
        rep = R.WireBytesBudget(axis="region", record_elems=3).check(
            _trace_copy())
        assert not rep.ok and "no collectives" in rep.detail


class TestUnknownTrips:
    """A data-dependent while bound may not silently count as 1."""

    def _dynamic_while(self):
        def fn(n):
            return jax.lax.while_loop(
                lambda c: c[0] < c[1],
                lambda c: (c[0] + 1.0, c[1]),
                (jnp.float32(0.0), n))[0]

        return jax.make_jaxpr(fn)(jnp.float32(5.0))

    def test_trip_count_is_none(self):
        jx = self._dynamic_while()
        whiles = [e for e in jx.jaxpr.eqns if e.primitive.name == "while"]
        assert whiles and while_trip_count(whiles[0]) is None

    def test_loop_weighted_count_raises(self):
        jx = self._dynamic_while()
        with pytest.raises(UnknownTripError):
            count_primitive(jx, "add", loop_weighted=True)
        # un-weighted per-trace counting still works
        assert count_primitive(jx, "add", loop_weighted=False) >= 1

    def test_primitive_budget_fails_loudly(self):
        rep = PrimitiveBudget("add", max=100,
                              loop_weighted=True).check(self._dynamic_while())
        assert not rep.ok
        assert "unknown" in rep.detail


class TestBaseline:
    def test_committed_baseline_matches_derived(self):
        results = R.check_against_baseline()
        bad = [r for r in results if not r.ok]
        assert not bad, "\n".join(
            f"{r.entry}/{r.quantity}: {r.detail}" for r in bad)
        # the acceptance surface: every entry reports the core quantities
        entries = {r.entry for r in results}
        assert any(e.startswith("hierarchy.refresh") for e in entries)
        quantities = {r.quantity for r in results}
        assert {"vmem_peak_bytes", "hbm_read_bytes", "hbm_passes",
                "flops"} <= quantities
        assert any(q.startswith("wire.region.") for q in quantities)

    def test_missing_baseline_fails_with_instruction(self, tmp_path):
        (res,) = R.check_against_baseline(path=str(tmp_path / "nope.json"))
        assert not res.ok and "--bless-resources" in res.detail

    def test_regression_carries_delta(self, tmp_path):
        derived = {"e[x]": {"flops": 110}}
        path = tmp_path / "base.json"
        R.bless({"e[x]": {"flops": 100}}, str(path))
        (res,) = [r for r in R.check_against_baseline(derived, str(path))
                  if not r.ok]
        assert res.quantity == "flops" and "+10.0%" in res.detail
        assert res.rule() == "resources:flops"
