"""The fused fold's operations and bytes at one shape worked by hand."""

import pytest

from bench_tiny import spec
from harness import peaks

STREAM = {"p": 8, "q": 2, "halfwidth": 1, "precision": "fp32",
          "compression": {"epsilon": 0.1, "emit_reconstruction": False},
          "detection": {"emit_statistics": False}}


def test_fused_stream_ops_and_bytes_by_hand():
    roof = spec.load_module("roofline", "fused_stream")
    rows, slots = 16, 2
    # band fold: 2 * 16 rows * 8 sensors * 3 diagonals = 768; project and
    # reconstruct: 4 * 16 * 8 * 2 = 1024
    assert roof.flops(rows, 8, 2, 1, stages=True) == 768 + 1024
    assert roof.flops(rows, 8, 2, 1, stages=False) == 768
    # x: 16*8*4 = 512; band: 2 slots * 3 * 8 * 4 = 192; z: 16*2*4 = 128;
    # T^2 and SPE: 16*2*4 = 128
    assert roof.bytes_moved(rows, slots, STREAM) == 512 + 192 + 128 + 128
    emit = dict(STREAM, compression={"epsilon": 0.1})
    assert roof.bytes_moved(rows, slots, emit) == 960 + 2 * 512
    bf16 = dict(STREAM, precision="bf16")
    assert roof.bytes_moved(rows, slots, bf16) == 960 - 256


def test_least_time_names_its_bound():
    roof = spec.load_module("roofline", "fused_stream")
    pk = peaks.peaks("TPU v5 lite")
    t, bound = roof.least_seconds(16, 2, {"stream": STREAM}, pk)
    assert bound == "bytes"
    assert t == pytest.approx(960 / 819e9)
    wide = dict(STREAM, p=1024, q=32, halfwidth=128)
    t, bound = roof.least_seconds(65536, 256, {"stream": wide}, pk)
    flops = 2 * 65536 * 1024 * 257 + 4 * 65536 * 1024 * 32
    nbytes = (65536 * 1024 * 4 + 256 * 257 * 1024 * 4 + 65536 * 32 * 4
              + 65536 * 8)
    assert t == pytest.approx(max(flops / 197e12, nbytes / 819e9))


def test_unknown_device_has_no_peaks():
    with pytest.raises(ValueError):
        peaks.peaks("TPU v9 imaginary")
