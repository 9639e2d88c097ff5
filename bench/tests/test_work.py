"""Work counts, peaks and the end-to-end arithmetic on known shapes."""
import pytest

from lib import peaks, work
from lib.traffic import Op


def test_field_bytes_and_chunks():
    assert work.field_bytes((1 << 23,)) == 32 << 20
    assert work.chunks_per_field((1 << 23,), 32 << 20) == 1
    assert work.field_bytes((256, 256, 256)) == 64 << 20
    assert work.chunks_per_field((256, 256, 256), 32 << 20) == 2
    assert work.chunks_per_field((1800, 3600), 32 << 20) == 1
    assert work.chunks_per_field((8779809,), 32 << 20) == 2


def test_roofline_counts_raw_plus_stream_bytes_over_hbm_peak():
    ops = [Op(0.0, 1.0, raw_bytes=32 << 20, stream_bytes=4 << 20)] * 3
    assert work.io_bytes(ops) == 3 * (36 << 20)
    # 819e6 bytes take 1 ms at 819 GB/s: 100% of a 1 ms device time
    assert work.roofline_pct(819e6, 1e-3, "TPU v5 lite") \
        == pytest.approx(100.0)
    assert work.roofline_pct(819e6, 4e-3, "TPU v5 lite") \
        == pytest.approx(25.0)
    assert work.roofline_pct(819e6, 0.0, "TPU v5 lite") is None


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        peaks.peaks("TPU v9 imaginary")
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_rates_and_ratio():
    ops = [Op(0.0, 0.5, raw_bytes=10**9, stream_bytes=10**8)] * 4
    assert work.rate_GBps(ops, 2.0) == pytest.approx(2.0)
    assert work.ratio(ops) == pytest.approx(10.0)
    assert work.rate_GBps([], 1.0) is None and work.ratio([]) is None
