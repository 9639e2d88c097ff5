"""The readers of the program's leaf spans and transfer / pass-size
counters: idle time split by leaf kind against a small trace recorded on
the chip, and the counter ratios against a registry filled by hand."""
import glob
import os

import pytest

from lib import harness, xtrace

HERE = os.path.dirname(os.path.abspath(__file__))
(RECORDED,) = glob.glob(os.path.join(HERE, "data", "hacc_dump_1op.xplane.pb"))


@pytest.fixture(scope="module")
def reduced():
    from jax.profiler import ProfileData
    return xtrace.reduce_profile(ProfileData.from_file(RECORDED), [0], 0.0)


def _ctx(trace=None, spans=(), op="dump"):
    return harness.Context({"name": "hacc." + op}, {}, {"op": op}, [],
                           (0.0, 1.0), "TPU v5 lite", trace, list(spans))


def _idle(ctx, kind):
    return harness.reader({"transfer": "idle_in_transfer.dump",
                           "host": "idle_in_host.dump",
                           None: "idle_unattributed.dump"}[kind])(ctx)


def _longest_gap(r):
    return max(r.gaps(), key=lambda g: g[1] - g[0])


def test_overlapping_leaves_of_two_kinds_split_a_gap(reduced):
    r = reduced
    a, b = _longest_gap(r)
    off = r.host_offset
    third = (b - a) / 3
    # a transfer leaf over the first two thirds of the gap, a host leaf
    # (another thread) over the last two: the middle third is both
    spans = [("fused.d2h", a - off, a + 2 * third - off),
             ("engine.commit", a + third - off, b - off),
             ("engine.queue_wait", a - off, b - off)]   # no leaf: ignored
    ctx = _ctx(r, spans)
    w = r.window_s
    assert _idle(ctx, "transfer") == pytest.approx(100 * 2 * third / w)
    assert _idle(ctx, "host") == pytest.approx(100 * third / w)
    idle_share = harness.reader("idle_share.dump")(ctx)
    total = sum(_idle(ctx, k) for k in ("transfer", "host", None))
    assert total == pytest.approx(idle_share, abs=1e-9)
    assert _idle(ctx, None) == pytest.approx(
        idle_share - 100 * (b - a) / w, abs=1e-9)


def test_wait_leaves_and_busy_time_count_for_nothing(reduced):
    r = reduced
    off = r.host_offset
    w0, w1 = r.window
    # a wait leaf over the whole window, a host leaf over the whole
    # window: only the idle part of the host leaf counts
    spans = [("fused.device_wait", w0 - off, w1 - off),
             ("fused.assemble", w0 - off, w1 - off)]
    ctx = _ctx(r, spans)
    assert _idle(ctx, "transfer") == 0
    assert _idle(ctx, "host") == pytest.approx(
        harness.reader("idle_share.dump")(ctx))
    assert _idle(ctx, None) == pytest.approx(0, abs=1e-9)


def test_idle_readers_read_nothing_without_the_program_table(
        reduced, monkeypatch):
    import repro.obs.trace as ot
    a, b = _longest_gap(reduced)
    spans = [("fused.d2h", a - reduced.host_offset,
              b - reduced.host_offset)]
    assert _idle(_ctx(reduced, spans), "transfer") > 0
    monkeypatch.delattr(ot, "LEAF_KINDS")
    for kind in ("transfer", "host", None):
        assert _idle(_ctx(reduced, spans), kind) is None
    # nor without a trace, or without spans
    monkeypatch.undo()
    assert _idle(_ctx(None, spans), "host") is None
    assert _idle(_ctx(reduced, []), "host") is None


@pytest.fixture()
def registry(monkeypatch):
    from repro.obs import metrics as om
    reg = om.MetricsRegistry()
    monkeypatch.setattr(om, "DEFAULT", reg)
    return reg


def test_pass_fill_reads_its_side(registry):
    registry.counter("ceaz_pass_values_total", side="encode",
                     op="ceaz_chunk").add(2 * 8388608)
    registry.counter("ceaz_pass_live_values_total", side="encode",
                     op="ceaz_chunk").add(8779809)
    registry.counter("ceaz_pass_values_total", side="decode",
                     op="ceaz_chunk_dec").add(1000)
    registry.counter("ceaz_pass_live_values_total", side="decode",
                     op="ceaz_chunk_dec").add(250)
    read = harness.reader("pass_fill.dump")
    assert read(_ctx(op="dump")) == pytest.approx(
        100 * 8779809 / (2 * 8388608))
    assert harness.reader("pass_fill.load")(_ctx(op="load")) == 25.0


def test_host_device_bytes_per_raw_reads_its_side(registry):
    for name, side, site, n in [
            ("ceaz_d2h_bytes_total", "encode", "engine.stage_in", 400),
            ("ceaz_h2d_bytes_total", "encode", "fused.h2d", 410),
            ("ceaz_d2h_bytes_total", "encode", "fused.d2h", 190),
            ("ceaz_h2d_bytes_total", "decode", "fused_decode.h2d", 60),
            ("ceaz_d2h_bytes_total", "decode", "fused_decode.d2h", 840)]:
        registry.counter(name, side=side, site=site).add(n)
    registry.counter("ceaz_raw_bytes_total").add(400)
    registry.counter("ceaz_decoded_bytes_total").add(450)
    read = harness.reader("host_device_bytes_per_raw.dump")
    assert read(_ctx(op="dump")) == pytest.approx(1000 / 400)
    assert read(_ctx(op="load")) == pytest.approx(900 / 450)


def test_counter_readers_read_nothing_without_the_counters(registry):
    for name in ("pass_fill.dump", "host_device_bytes_per_raw.dump"):
        assert harness.reader(name)(_ctx(op="dump")) is None
    # a mix whose side the readers do not know
    registry.counter("ceaz_pass_values_total", side="encode").add(1)
    assert harness.reader("pass_fill.dump")(_ctx(op="gather")) is None
