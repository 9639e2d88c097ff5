"""Leaf spans, transfer and pass-size counters of the dump and load
paths (docs/OBSERVABILITY.md, "Leaf spans"), and the shared clock: a
span traced while JAX is imported lands on a `jax.profiler` trace's
host plane.

Each path runs once per field shape in a module fixture: a 1-D field
whose last chunk is a short tail, and a 3-D field (the stage-composed
N-D bank pass). The field goes in as a device array, as a simulation
rank's field does."""
import glob
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import CEAZ, CEAZConfig
from repro.io import engine as E
from repro.obs import metrics as om
from repro.obs import trace as ot
from repro.runtime import fused as F
from repro.runtime import fused_decode as FD

CHUNK_VALUES = 1 << 15
BLOCK = 4096
SHAPES = {"1d_tail": (2 * CHUNK_VALUES + 3001,), "3d": (32, 32, 64)}


def _comp():
    return CEAZ(CEAZConfig(mode="rel", eb=1e-4, codebook="bank",
                           use_fused=True, chunk_bytes=4 * CHUNK_VALUES,
                           block_size=BLOCK, bank_drift_tol=float("inf")))


def _field(shape):
    rng = np.random.default_rng(13)
    x = np.cumsum(rng.standard_normal(int(np.prod(shape))))
    return x.reshape(shape).astype(np.float32)


def _counters(snap, name, **labels):
    """Sum of a counter over the label sets that hold `labels`."""
    out = 0
    for m in om.DEFAULT.metrics():
        if m.name == name and all(dict(m.labels).get(k) == v
                                  for k, v in labels.items()):
            out += snap.get(m.fullname, 0)
    return out


class _Run:
    def __init__(self, events, before, after, **info):
        self.events, self.info = events, info
        self.before, self.after = before, after

    def delta(self, name, **labels):
        return (_counters(self.after, name, **labels)
                - _counters(self.before, name, **labels))

    def spans(self, name):
        return [e for e in self.events if e["name"] == name]


def _traced(fn):
    ot.disable()
    tracer = ot.enable(save_at_exit=False)
    tracer.clear()
    before = om.snapshot()
    try:
        info = fn()
        return _Run(tracer.events(), before, om.snapshot(), **info)
    finally:
        ot.disable()


@pytest.fixture(scope="module", params=sorted(SHAPES))
def dump(request, tmp_path_factory):
    shape = SHAPES[request.param]
    x = _field(shape)
    path = str(tmp_path_factory.mktemp("dump") / "f.ceazs")
    comp = _comp()
    E.write_stream(path, [jnp.asarray(x)], comp)       # compile outside
    pulls = []
    orig = F.to_host

    def spy(side, site, *arrays):
        out = orig(side, site, *arrays)
        pulls.append((side, site, sum(a.nbytes for a in out
                                      if a is not None)))
        return out

    F.to_host = spy
    try:
        run = _traced(lambda: {
            "stats": E.write_stream(path, [jnp.asarray(x)], comp)})
    finally:
        F.to_host = orig
    run.info.update(shape=shape, x=x, pulls=pulls, path=path,
                    bank=comp.bank, kind=request.param)
    return run


@pytest.fixture(scope="module", params=sorted(SHAPES))
def load(request, tmp_path_factory):
    shape = SHAPES[request.param]
    x = _field(shape)
    path = str(tmp_path_factory.mktemp("load") / "f.ceazs")
    E.write_stream(path, [x], _comp())
    E.read_stream_arrays(path)                          # compile outside
    staged = []
    orig = FD._ChunkBatch._stage_mega

    def spy(self):
        out = orig(self)
        staged.append(out)
        return out

    FD._ChunkBatch._stage_mega = spy
    try:
        run = _traced(lambda: {"out": E.read_stream_arrays(path)})
    finally:
        FD._ChunkBatch._stage_mega = orig
    run.info.update(shape=shape, x=x, staged=staged, kind=request.param)
    return run


def _inside(inner, outer):
    return (inner["tid"] == outer["tid"] and outer["ts"] <= inner["ts"]
            and inner["ts"] + inner["dur"]
            <= outer["ts"] + outer["dur"] + 1e-3)


def test_leaf_kinds_table():
    assert set(ot.LEAF_KINDS.values()) == {"transfer", "host", "wait"}
    assert all(n.count(".") == 1 for n in ot.LEAF_KINDS)


# (leaf, the span it runs under: None where its thread has no outer span)
ENCODE_LEAVES = [
    ("engine.stage_in", None),
    ("ceaz.policy", "ceaz.compress"),
    ("fused.h2d", "ceaz.compress"),
    ("fused.device_wait", "ceaz.compress"),
    ("fused.d2h", "ceaz.compress"),
    ("fused.host_select", "ceaz.compress"),
    ("fused.assemble", "ceaz.compress"),
    ("engine.serialize", None),
    ("engine.commit", None),
    ("engine.finalize", None),
]
DECODE_LEAVES = [
    ("reader.prefetch", None),
    ("fused_decode.stage", "reader.decode_group"),
    ("fused_decode.h2d", "reader.decode_group"),
    ("fused_decode.device_wait", "reader.decode_group"),
    ("fused_decode.d2h", "reader.decode_group"),
    ("fused_decode.finish", "reader.decode_group"),
]


@pytest.mark.parametrize("leaf,outer", ENCODE_LEAVES,
                         ids=[n for n, _ in ENCODE_LEAVES])
def test_encode_leaf_span(dump, leaf, outer):
    spans = dump.spans(leaf)
    assert spans, f"no {leaf} span"
    assert leaf in ot.LEAF_KINDS
    if outer is not None:
        outers = dump.spans(outer)
        assert all(any(_inside(s, o) for o in outers) for s in spans)
        # and under the engine's compress stage
        if outer == "ceaz.compress":
            assert all(any(_inside(s, o)
                           for o in dump.spans("engine.compress"))
                       for s in spans)


@pytest.mark.parametrize("leaf,outer", DECODE_LEAVES,
                         ids=[n for n, _ in DECODE_LEAVES])
def test_decode_leaf_span(load, leaf, outer):
    spans = load.spans(leaf)
    assert spans, f"no {leaf} span"
    assert leaf in ot.LEAF_KINDS
    if outer is not None:
        outers = load.spans(outer)
        assert all(any(_inside(s, o) for o in outers) for s in spans)


def _start(run, name):
    (s,) = run.spans(name)
    return s["ts"], s["ts"] + s["dur"]


def test_encode_leaves_in_order(dump):
    op = "kernel.ceaz_chunk" if len(dump.info["shape"]) == 1 \
        else "kernel.hufenc"
    order = ["ceaz.policy", "fused.h2d", op, "fused.device_wait",
             "fused.d2h", "fused.host_select", "fused.assemble"]
    spans = [_start(dump, n) for n in order]
    for (a0, a1), (b0, _) in zip(spans, spans[1:]):
        assert a1 <= b0 + 1e-3


def test_decode_leaves_in_order(load):
    order = ["fused_decode.h2d", "kernel.ceaz_chunk_dec",
             "fused_decode.device_wait", "fused_decode.d2h",
             "fused_decode.finish"]
    spans = [_start(load, n) for n in order]
    for (a0, a1), (b0, _) in zip(spans, spans[1:]):
        assert a1 <= b0 + 1e-3


def test_stage_in_counts_device_bytes(dump):
    n = int(np.prod(dump.info["shape"]))
    assert dump.delta(om.D2H_BYTES, side="encode",
                      site="engine.stage_in") == 4 * n


def test_stage_in_counts_nothing_for_host_shards(tmp_path):
    before = om.snapshot()
    E.write_stream(str(tmp_path / "h.ceazs"), [_field((5000,))], _comp())
    run = _Run([], before, om.snapshot())
    assert run.delta(om.D2H_BYTES, site="engine.stage_in") == 0


def test_encode_h2d_bytes_are_work_and_bank_tables(dump):
    n = int(np.prod(dump.info["shape"]))
    bank = dump.info["bank"]
    expect = 4 * n + 4 * bank.lengths.size + 4 * bank.code_table().size
    assert dump.delta(om.H2D_BYTES, side="encode",
                      site="fused.h2d") == expect


def test_encode_d2h_bytes_are_the_pulled_results(dump):
    pulled = sum(b for side, site, b in dump.info["pulls"]
                 if (side, site) == ("encode", "fused.d2h"))
    assert pulled > 0
    assert dump.delta(om.D2H_BYTES, side="encode",
                      site="fused.d2h") == pulled


def test_encode_pass_fill_is_live_over_sized(dump):
    n = int(np.prod(dump.info["shape"]))
    n_chunks = -(-n // CHUNK_VALUES)
    sized = dump.delta(om.PASS_VALUES, side="encode")
    live = dump.delta(om.PASS_LIVE_VALUES, side="encode")
    assert sized == n_chunks * CHUNK_VALUES and live == n
    assert live / sized == n / (n_chunks * CHUNK_VALUES)
    if dump.info["kind"] == "3d":
        assert live == sized                # two whole chunks


def test_decode_h2d_bytes_are_the_staged_arrays(load):
    (staged,) = load.info["staged"]
    assert load.delta(om.H2D_BYTES, side="decode",
                      site="fused_decode.h2d") == sum(a.nbytes
                                                      for a in staged)


def test_decode_d2h_bytes_are_the_q_rows(load):
    n = int(np.prod(load.info["shape"]))
    n_chunks = -(-n // CHUNK_VALUES)
    # a 1-D chain comes back as whole int32 chunk rows; a 3-D field as
    # its flat cumsum
    expect = 4 * (n_chunks * CHUNK_VALUES if load.info["kind"] == "1d_tail"
                  else n)
    assert load.delta(om.D2H_BYTES, side="decode",
                      site="fused_decode.d2h") == expect


def test_decode_pass_fill_is_live_over_bucketed(load):
    n = int(np.prod(load.info["shape"]))
    (staged,) = load.info["staged"]
    c_cap, nb_cap = staged[1].shape
    assert c_cap == FD._bucket_pow2(-(-n // CHUNK_VALUES))
    assert nb_cap == FD._bucket_pow2(CHUNK_VALUES // BLOCK)
    sized = load.delta(om.PASS_VALUES, side="decode", op="ceaz_chunk_dec")
    live = load.delta(om.PASS_LIVE_VALUES, side="decode",
                      op="ceaz_chunk_dec")
    assert (sized, live) == (c_cap * nb_cap * BLOCK, n)


def test_decoded_bytes_count_on_the_fused_path(load):
    (a,) = load.info["out"]
    np.testing.assert_allclose(a, load.info["x"],
                               atol=1e-4 * float(np.ptp(load.info["x"])))
    assert load.delta(om.DECODED_BYTES) == load.info["x"].nbytes


def test_span_on_profiler_host_plane(tmp_path):
    """While JAX is imported, a span is also a TraceAnnotation: it shows
    on the trace's host plane, at its perf_counter start mapped through
    the offset of a `bench.window` annotation (as the benchmark maps
    spans onto the profiler clock)."""
    from jax.profiler import ProfileData
    ot.disable()
    tracer = ot.enable(save_at_exit=False)
    tracer.clear()
    try:
        jax.profiler.start_trace(str(tmp_path))
        w0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.window"):
            time.sleep(0.002)
            with ot.span("fused.d2h"):
                np.asarray(jnp.arange(1024) + 1)
            time.sleep(0.002)
        jax.profiler.stop_trace()
        (ev,) = tracer.events()
    finally:
        ot.disable()
    (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                        recursive=True)
    starts = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    starts.setdefault(e.name, e.start_ns * 1e-9)
    assert "fused.d2h" in starts and "bench.window" in starts
    offset = starts["bench.window"] - w0
    mapped = tracer._t0 + ev["ts"] * 1e-6 + offset
    assert abs(mapped - starts["fused.d2h"]) < 1e-3


def test_span_without_tracer_is_no_annotation():
    ot.disable()
    assert ot.span("fused.d2h") is ot.span("fused.h2d")


@pytest.mark.parametrize("kind", sorted(SHAPES))
def test_device_stats_pull_dense_deltas_in_d2h_on_literal_overflow(
        kind, monkeypatch):
    """With the pass's statistics on the device (the path a TPU takes),
    more literal candidates than the pass keeps send the literal check
    to the dense deltas: that pull belongs to `fused.d2h` and its bytes
    count there, the check counts as dense, and the stream is the
    host-statistics path's. The capacity is held at its 1/256 floor,
    which this field's guard band overflows."""
    from conftest import assert_streams_bit_identical
    x = _field(SHAPES[kind])
    comp = _comp()
    ref = comp.compress(x)
    monkeypatch.setattr(F, "_default_stats_on_device", lambda: True)
    floor = F.literal_capacity
    monkeypatch.setattr(F, "literal_capacity",
                        lambda n, eb=None, amax=None: floor(n))
    pulls = []
    orig = F.to_host

    def spy(side, site, *arrays):
        out = orig(side, site, *arrays)
        pulls.append([a.shape for a in out if a is not None])
        return out

    monkeypatch.setattr(F, "to_host", spy)
    run = _traced(lambda: {"c": comp.compress(x)})
    assert_streams_bit_identical(ref, run.info["c"])
    n = x.size
    n_chunks = -(-n // CHUNK_VALUES)
    assert [(n_chunks, CHUNK_VALUES)] in pulls      # the dense deltas
    assert run.delta(om.D2H_BYTES, side="encode", site="fused.d2h") \
        >= 4 * n_chunks * CHUNK_VALUES
    assert run.delta(om.LITERAL_DENSE_CHECKS, side="encode") == 1
    assert len(run.spans("fused.d2h")) == 1
