"""Build the system under test from a configuration file.

The configuration's ``stream`` group is ``repro.streaming.StreamConfig``
(with ``compression``/``detection`` as their own config objects) and its
``engine`` group the ``StreamingPCAEngine`` arguments; telemetry is always
on.
"""

from __future__ import annotations


def stream_config(config: dict):
    from repro.streaming import StreamConfig
    from repro.streaming.compressor import CompressionConfig
    from repro.streaming.detector import DetectionConfig
    s = dict(config["stream"])
    if s.get("compression") is not None:
        s["compression"] = CompressionConfig(**s["compression"])
    if s.get("detection") is not None:
        s["detection"] = DetectionConfig(**s["detection"])
    return StreamConfig(**s)


def build_engine(config: dict, seed: int):
    from repro.serve.engine import StreamingPCAEngine
    e = config["engine"]
    return StreamingPCAEngine(stream_config(config), slots=e["slots"],
                              seed=seed, chunk=e["chunk"],
                              pipeline=e["pipeline"], telemetry=True)
