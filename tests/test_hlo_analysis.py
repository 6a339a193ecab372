"""HLO collective parsing + loop-aware trip-count correction + roofline."""

import numpy as np
import pytest

from repro.launch import hlo_analysis as H


SAMPLE_HLO = """
%wrapped_add (a: f32[], b: f32[]) -> f32[] {
  ROOT %add = f32[] add(%a, %b)
}

%body_spmd (param: (s32[], f32[128,256])) -> (s32[], f32[128,256]) {
  %p = (s32[], f32[128,256]) parameter(0)
  %x = f32[128,256] get-tuple-element(%p), index=1
  %ag = f32[128,1024]{1,0} all-gather(%x), channel_id=1, replica_groups=[64,4]<=[256], dimensions={1}
  %ar = f32[128,256] all-reduce(%x), channel_id=2, replica_groups=[16,16]<=[256], to_apply=%wrapped_add
  ROOT %t = (s32[], f32[128,256]) tuple(%x, %ar)
}

%cond_spmd (param.1: (s32[], f32[128,256])) -> pred[] {
  %p1 = (s32[], f32[128,256]) parameter(0)
  %i = s32[] get-tuple-element(%p1), index=0
  %constant.9 = s32[] constant(12)
  ROOT %cmp = pred[] compare(%i, %constant.9), direction=LT
}

ENTRY %main_spmd (a: f32[128,256]) -> f32[128,256] {
  %a = f32[128,256] parameter(0)
  %w = (s32[], f32[128,256]) while(%tup), condition=%cond_spmd, body=%body_spmd
  %cp = f32[128,256] collective-permute(%a), channel_id=3, source_target_pairs={{0,1},{1,2}}
  ROOT %out = f32[128,256] get-tuple-element(%w), index=1
}
"""


class TestCollectiveParse:
    def test_counts(self):
        st = H.parse_collectives(SAMPLE_HLO, 256, loop_aware=False)
        assert st.counts["all-gather"] == 1
        assert st.counts["all-reduce"] == 1
        assert st.counts["collective-permute"] == 1

    def test_wire_model_naive(self):
        st = H.parse_collectives(SAMPLE_HLO, 256, loop_aware=False)
        ag = 128 * 1024 * 4 * (3 / 4)          # result N * (g-1)/g, g=4
        ar = 2 * 128 * 256 * 4 * (15 / 16)     # 2N(g-1)/g, g=16
        cp = 128 * 256 * 4
        assert st.wire_bytes["all-gather"] == pytest.approx(ag)
        assert st.wire_bytes["all-reduce"] == pytest.approx(ar)
        assert st.wire_bytes["collective-permute"] == pytest.approx(cp)

    def test_loop_aware_scales_body_by_trip_count(self):
        naive = H.parse_collectives(SAMPLE_HLO, 256, loop_aware=False)
        aware = H.parse_collectives(SAMPLE_HLO, 256, loop_aware=True)
        assert aware.loop_corrected
        # body collectives x12, top-level permute x1
        assert aware.wire_bytes["all-gather"] == pytest.approx(
            12 * naive.wire_bytes["all-gather"])
        assert aware.wire_bytes["all-reduce"] == pytest.approx(
            12 * naive.wire_bytes["all-reduce"])
        assert aware.wire_bytes["collective-permute"] == pytest.approx(
            naive.wire_bytes["collective-permute"])

    def test_loop_aware_on_real_compile(self):
        """End-to-end: scan with known trip count on the 1-device mesh."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        mesh = jax.make_mesh((1,), ("model",))

        def fn(x):
            def body(c, _):
                return c * 2.0, None
            out, _ = jax.lax.scan(body, x, None, length=9)
            return out.sum()

        x = jax.ShapeDtypeStruct((64,), jnp.float32,
                                 sharding=NamedSharding(mesh, P(None)))
        compiled = jax.jit(fn).lower(x).compile()
        st = H.parse_collectives(compiled.as_text(), 1, loop_aware=True)
        # single device: no collectives, but the parse must not crash and
        # must detect loop structure
        assert st.total_wire_bytes == 0.0


class TestUnknownTrips:
    """A while condition with no integer constant (data-dependent bound)
    must surface as *unknown*, not silently count as 1."""

    # same program, but the condition compares two loop-carried values
    UNKNOWN_HLO = SAMPLE_HLO.replace(
        "%constant.9 = s32[] constant(12)",
        "%constant.9 = s32[] get-tuple-element(%p1), index=0")

    def test_fallback_trip_policy(self):
        assert H.fallback_trip([3, 12]) == 12
        assert H.fallback_trip([0]) == 1          # floor
        assert H.fallback_trip(()) is None        # unknown, not 1

    def test_unknown_body_recorded_with_x1_floor(self):
        naive = H.parse_collectives(SAMPLE_HLO, 256, loop_aware=False)
        st = H.parse_collectives(self.UNKNOWN_HLO, 256, loop_aware=True)
        assert st.unknown_trips == ("body_spmd",)
        assert not st.trips_known
        # the floor: body contributes x1, same as the naive parse
        assert st.wire_bytes["all-gather"] == pytest.approx(
            naive.wire_bytes["all-gather"])

    def test_roofline_refuses_unknown_trips(self):
        st = H.parse_collectives(self.UNKNOWN_HLO, 256, loop_aware=True)
        with pytest.raises(ValueError, match="unknown_trip"):
            H.roofline_terms({"flops": 1e12, "bytes accessed": 1e9}, st)
        terms = H.roofline_terms({"flops": 1e12, "bytes accessed": 1e9},
                                 st, allow_unknown_trips=True)
        assert terms.compute_s > 0

    def test_explicit_bound_restores_certainty(self):
        naive = H.parse_collectives(self.UNKNOWN_HLO, 256, loop_aware=False)
        st = H.parse_collectives(self.UNKNOWN_HLO, 256, loop_aware=True,
                                 unknown_trip=12)
        assert st.trips_known
        assert st.wire_bytes["all-reduce"] == pytest.approx(
            12 * naive.wire_bytes["all-reduce"])
        H.roofline_terms({"flops": 1e12, "bytes accessed": 1e9}, st)


class TestRoofline:
    def test_terms_and_dominance(self):
        st = H.parse_collectives(SAMPLE_HLO, 256, loop_aware=False)
        terms = H.roofline_terms({"flops": 1e15, "bytes accessed": 1e9}, st)
        assert terms.compute_s == pytest.approx(1e15 / H.PEAK_FLOPS)
        assert terms.memory_s == pytest.approx(1e9 / H.HBM_BW)
        assert terms.dominant == "compute"
        assert terms.bound_s == terms.compute_s


class TestDevicePeaks:
    def test_peaks_keyed_by_device_kind(self):
        v5e = H.device_peaks("TPU v5 lite")
        assert (v5e.flops, v5e.hbm_bw) == (197e12, 819e9)
        assert (H.PEAK_FLOPS, H.HBM_BW) == (v5e.flops, v5e.hbm_bw)

    @pytest.mark.parametrize("kind", ["cpu", "TPU v4", ""])
    def test_unknown_device_raises(self, kind):
        with pytest.raises(ValueError, match="no published peaks"):
            H.device_peaks(kind)


class TestCompileCache:
    def test_env_var_wins_and_nothing_else_is_set(self, monkeypatch,
                                                   tmp_path):
        import jax

        from repro.launch import compile_cache
        before = jax.config.jax_compilation_cache_dir
        monkeypatch.setenv(compile_cache.CACHE_ENV, str(tmp_path))
        assert compile_cache.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before

    def test_default_is_a_fixed_path_in_the_checkout(self, monkeypatch):
        import jax

        from repro.launch import compile_cache
        before = jax.config.jax_compilation_cache_dir
        monkeypatch.delenv(compile_cache.CACHE_ENV, raising=False)
        try:
            path = compile_cache.enable_compile_cache()
            assert path == compile_cache.enable_compile_cache()
            assert path.endswith(".jax_cache")
            assert jax.config.jax_compilation_cache_dir == path
        finally:
            jax.config.update("jax_compilation_cache_dir", before)
