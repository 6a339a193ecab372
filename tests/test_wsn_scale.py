"""wsn-1m at fleet scale: config sanity and weak-scaling rows.

Acceptance for the wsn-1m config (DESIGN.md Sec. 13): the two-level shape
is internally consistent, and the weak-scaling benchmark emits the
>= 3-region-count curve plus the end-to-end wsn-1m smoke-replica row that
CI records as BENCH_scale.json.
"""

import os
import sys

import numpy as np
import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


class TestWSNConfig:
    def test_two_level_shape_consistent(self):
        from repro.configs.wsn_1m import CONFIG

        assert CONFIG.p == 1_048_576
        assert CONFIG.p % CONFIG.n_regions == 0
        assert CONFIG.region_p * CONFIG.n_regions == CONFIG.p
        # a region must be wider than the covariance band it maintains
        assert CONFIG.region_p > 2 * CONFIG.halfwidth + 1
        assert CONFIG.q <= CONFIG.region_p

    def test_smoke_replica_preserves_ratios(self):
        from repro.configs.wsn_1m import CONFIG

        smoke = CONFIG.smoke()
        assert smoke.name == "wsn-1m-smoke"
        assert smoke.p % smoke.n_regions == 0
        assert smoke.region_p > 2 * smoke.halfwidth + 1
        assert smoke.q <= smoke.region_p
        # seconds-scale: small enough to stream end to end in CI
        assert smoke.p <= 8192 and smoke.batch_epochs <= 16

    def test_indivisible_regions_raise(self):
        import dataclasses

        from repro.configs.wsn_1m import CONFIG

        bad = dataclasses.replace(CONFIG, n_regions=1000)
        with pytest.raises(ValueError, match="divisible"):
            bad.region_p


class TestScaleBench:
    def test_weak_scaling_rows(self):
        if REPO not in sys.path:
            sys.path.insert(0, REPO)
        from benchmarks import scale_bench

        rows = scale_bench.run(smoke=True, regions=(1, 2))
        names = [r["name"] for r in rows]
        assert names == ["scale/regions1", "scale/regions2",
                         "scale/wsn_1m_smoke"]
        for r in rows:
            assert r["us_per_call"] > 0
            fields = r["derived"].split("|")
            assert len(fields) == 4
            assert "rounds/s" in fields[0]
            rho = float(fields[1].split()[-1])
            assert np.isfinite(rho) and 0.0 <= rho <= 1.0
