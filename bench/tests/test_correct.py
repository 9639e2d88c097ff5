"""`correct` comes out true for the program and false for the control
and for each fault the cell can have, with the harness's look for a
chip skipped and the rest of a run driven at a small size."""
import numpy as np
import pytest

from conftest import small_run
from lib import harness, named

CELLS = ["hacc.dump", "nyx.dump", "hacc.load"]


@pytest.mark.parametrize("workload", CELLS)
def test_program_is_correct(workload):
    r = small_run(workload)
    assert r["correct"], r["checks"]
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert list(r)[-1] == "checks"


@pytest.mark.parametrize("workload", CELLS)
def test_control_is_not_correct(workload):
    cell = harness.cell_of(harness.load_spec(), workload)
    op = harness.load_json(f"{harness.BENCH_DIR}/traffic/"
                           f"{cell['traffic']}.json")["op"]
    r = small_run(workload, hooks=named.module("ops", op).control())
    assert not r["correct"]
    (reading,) = (c["value"] for c in r["checks"].values())
    assert reading > 3.0


def _alter_write(monkeypatch, how):
    from repro.io import engine
    real = engine.write_stream
    prev = []

    def broken(path, shards, comp=None, **kw):
        x = np.array(shards[0])
        if how == "value":          # an answer altered where it is made
            x.reshape(-1)[x.size // 3] += 1.0
        elif how == "half":         # half of the batch left out
            x = x.reshape(-1)[: x.size // 2]
        else:                       # state left as the last call had it
            prev.append(x)
            x = prev[-2] if len(prev) > 1 else x
        return real(path, [x], comp, **kw)

    monkeypatch.setattr(engine, "write_stream", broken)


def _alter_read(monkeypatch, how):
    from repro.io import engine
    real = engine.read_stream_arrays

    prev = []

    def broken(path, *a, **kw):
        out = real(path, *a, **kw)
        x = out[0].copy()
        if how == "value":
            x.reshape(-1)[x.size // 3] += 1.0
        elif how == "half":
            x = x.reshape(-1)[: x.size // 2]
        else:
            prev.append(x)
            x = prev[-2] if len(prev) > 1 else x
        return [x]

    monkeypatch.setattr(engine, "read_stream_arrays", broken)


@pytest.mark.parametrize("workload", ["hacc.dump", "nyx.dump"])
@pytest.mark.parametrize("how", ["value", "half", "stale"])
def test_dump_fault_is_not_correct(workload, how, monkeypatch):
    _alter_write(monkeypatch, how)
    assert not small_run(workload)["correct"]


@pytest.mark.parametrize("how", ["value", "half", "stale"])
def test_load_fault_is_not_correct(how, monkeypatch):
    _alter_read(monkeypatch, how)
    assert not small_run("hacc.load")["correct"]


def test_failed_op_is_not_correct(monkeypatch):
    from repro.io import engine

    def boom(*a, **kw):
        raise RuntimeError("disk full")

    monkeypatch.setattr(engine, "write_stream", boom)
    with pytest.raises(RuntimeError):     # the warm-up dump fails first
        small_run("hacc.dump")
