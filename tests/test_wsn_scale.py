"""wsn-1m at fleet scale: config sanity, smoke dry-run, weak-scaling rows.

PR-level acceptance for the production config (DESIGN.md Sec. 13): the
two-level shape is internally consistent, every dry-run cell of the real
1M-sensor system lowers and compiles in smoke mode on forced host devices
(subprocess — device count locks at first jax init in this process), and
the weak-scaling benchmark emits the >= 3-region-count curve plus the
end-to-end wsn-1m smoke-replica row that CI records as BENCH_scale.json.
"""

import json
import os
import subprocess
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


class TestDryrunSmoke:
    def test_all_wsn_cells_compile(self, tmp_path):
        """The real wsn-1m cell list (cov/pim/transform/hier_merge) lowers
        and compiles at the smoke replica's shapes on 8 forced devices —
        the CI gate that the production config actually executes."""
        out = tmp_path / "dryrun_smoke.jsonl"
        env = dict(os.environ)
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        env["JAX_PLATFORMS"] = "cpu"     # forced host devices, not the chip
        env["PYTHONPATH"] = os.path.join(REPO, "src") + os.pathsep \
            + env.get("PYTHONPATH", "")
        proc = subprocess.run(
            [sys.executable, "-m", "repro.launch.dryrun", "--smoke",
             "--out", str(out)],
            capture_output=True, text=True, timeout=540, cwd=REPO, env=env)
        assert proc.returncode == 0, proc.stderr[-2000:]
        rows = [json.loads(line) for line in out.read_text().splitlines()]
        assert len(rows) == 5, [r["shape"] for r in rows]
        bad = [r for r in rows if not r["ok"]]
        assert not bad, [(r["shape"], r.get("error")) for r in bad]
        assert {r["shape"] for r in rows} == {
            "cov_update", "pim_block", "pim_deflated", "transform",
            "hier_merge"}


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
