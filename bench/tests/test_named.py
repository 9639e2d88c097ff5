"""Everything `BENCHMARK.json` names is found by name under `bench/`:
each configuration's proxy and reference, each mix's op, each metric's
reader."""
import json
import os

import pytest

from lib import harness, named

SPEC = harness.load_spec()


@pytest.mark.parametrize("cfg", SPEC["configs"], ids=lambda c: c["name"])
def test_config_names_its_proxy_and_reference(cfg):
    doc = harness.load_json(os.path.join(harness.ROOT, cfg["file"]))
    assert callable(named.module("proxies", doc["proxy"]).make)
    ref = named.module("references", doc["reference"])
    assert callable(ref.record_decoder) and callable(ref.readings)
    assert doc["limits"]


@pytest.mark.parametrize("cell", SPEC["workloads"], ids=lambda w: w["name"])
def test_mix_names_its_op(cell):
    mix = harness.load_json(os.path.join(
        harness.BENCH_DIR, "traffic", cell["traffic"] + ".json"))
    op = named.module("ops", mix["op"])
    assert callable(op.Traffic) and isinstance(op.control(), dict)


@pytest.mark.parametrize("metric", SPEC["end_to_end"] + SPEC["per_layer"],
                         ids=lambda m: m["name"])
def test_metric_has_a_reader(metric):
    if metric["name"] == "setup_s":     # taken by the harness itself
        return
    assert callable(harness.reader(metric["name"]))


def test_reader_falls_back_to_dotted_prefix_and_json(tmp_path):
    assert harness.reader("idle_share.some_new_family") \
        is harness.reader("idle_share.dump")
    with pytest.raises(FileNotFoundError):
        harness.reader("no_such_metric.dump")
    # a metric file of parameters reaches its shared reader with them
    path = os.path.join(harness.BENCH_DIR, "metrics",
                        "ceaz_chunk_roofline.json")
    assert json.load(open(path))["reader"] == "roofline"
