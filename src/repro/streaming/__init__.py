"""Streaming distributed PCA: online covariance + drift-triggered refreshes.

The paper computes principal components from a covariance snapshot; this
package is the *online* continuation (DESIGN.md Sec. 8): sensor rounds arrive
continuously, the banded sufficient statistics are folded in place with an
exponential forgetting factor, and the basis is recomputed only when the
retained variance of the current components drifts past a threshold — the
accuracy-vs-communication tradeoff of the paper's Secs. 3-4 replayed in time.

Submodules
----------
online_cov   OnlineCovariance state + forgetting-factor updates (Pallas
             cov-update kernel on the hot path) and the ``lax.scan`` driver
scheduler    RecomputeScheduler: retained-variance drift monitor +
             orthogonal-iteration basis refresh with Table-1 cost accounting
compressor   ε-supervised compression stage (Sec. 2.4.1 on device): fused
             Pallas project/reconstruct/flag pass + uniform score quantizer
detector     T²/SPE event-detection stage (Sec. 2.4.3 on device): fused
             Pallas monitoring pass + Wilson-Hilferty thresholds with
             healthy-window recalibration after every basis refresh
driver       single-network stream loops (the reference), the fleet body
             behind the batched multi-network driver and the engine's
             step, and the ``shard_map`` sharded runner
hierarchy    two-level million-sensor fleets (DESIGN.md Sec. 13): per-region
             streaming + cross-host energy-merge collectives over the
             ``region`` mesh axis, Table-1 merge billing
"""

from repro.streaming.online_cov import (
    OnlineCovariance, online_init, online_update, online_update_chunk,
    online_estimate, stream_covariance,
)
from repro.streaming.scheduler import (
    RecomputeScheduler, SchedulerState, retained_fraction, ortho_refresh,
    ortho_refresh_evals,
)
from repro.streaming.compressor import (
    CompressionConfig, RoundCompression, quantize_scores, compress_round,
)
from repro.streaming.detector import (
    DetectionConfig, DetectorState, RoundDetection, detect_round,
    detector_init, wilson_hilferty,
)
from repro.streaming.driver import (
    StreamConfig, StreamState, RoundMetrics, stream_init, stream_step,
    chunk_stream_step, stream_run, chunked_stream_run, batched_stream_run,
    sharded_stream_run,
)
from repro.streaming.hierarchy import (
    FleetBasis, FleetMerge, region_energies, merge_fleet, fleet_basis_dense,
    hierarchical_stream_init, hierarchical_stream_run,
)

__all__ = [
    "OnlineCovariance", "online_init", "online_update",
    "online_update_chunk", "online_estimate", "stream_covariance",
    "RecomputeScheduler", "SchedulerState", "retained_fraction",
    "ortho_refresh", "ortho_refresh_evals",
    "CompressionConfig", "RoundCompression", "quantize_scores",
    "compress_round",
    "DetectionConfig", "DetectorState", "RoundDetection", "detect_round",
    "detector_init", "wilson_hilferty",
    "StreamConfig", "StreamState", "RoundMetrics", "stream_init",
    "stream_step", "chunk_stream_step", "stream_run", "chunked_stream_run",
    "batched_stream_run", "sharded_stream_run",
    "FleetBasis", "FleetMerge", "region_energies", "merge_fleet",
    "fleet_basis_dense", "hierarchical_stream_init",
    "hierarchical_stream_run",
]
