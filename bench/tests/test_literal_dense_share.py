"""The reader of the encode's dense literal checks against a registry
filled by hand: nothing without the program's counters, 0 when every
check took the candidate branch, 100 when every check ran densely."""
import pytest

from lib import harness


@pytest.fixture()
def registry(monkeypatch):
    from repro.obs import metrics as om
    reg = om.MetricsRegistry()
    monkeypatch.setattr(om, "DEFAULT", reg)
    return reg


def _ctx(op="dump"):
    return harness.Context({"name": "hacc." + op}, {}, {"op": op}, [],
                           (0.0, 1.0), "TPU v5 lite", None, [])


def test_literal_dense_share_reads_the_encode_checks(registry):
    read = harness.reader("literal_dense_share.dump")
    registry.counter("ceaz_literal_checks_total", side="encode").add(6)
    assert read(_ctx("dump")) == 0.0
    registry.counter("ceaz_literal_dense_checks_total",
                     side="encode").add(3)
    assert read(_ctx("dump")) == 50.0
    registry.counter("ceaz_literal_dense_checks_total",
                     side="encode").add(3)
    assert read(_ctx("dump")) == 100.0


@pytest.mark.parametrize("op", ["dump", "load", "gather"])
def test_literal_dense_share_reads_nothing_without_the_counters(
        registry, op):
    assert harness.reader("literal_dense_share.dump")(_ctx(op)) is None


def test_literal_dense_share_reads_nothing_on_the_decode_side(registry):
    registry.counter("ceaz_literal_checks_total", side="encode").add(6)
    assert harness.reader("literal_dense_share.dump")(_ctx("load")) is None
