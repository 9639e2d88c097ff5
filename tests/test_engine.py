"""Async compression-I/O engine: ordered-commit byte-identity, overlap
accounting, backpressure, and crash-safety of the stream format
(truncated file, corrupted footer, corrupted payload, out-of-order
shard commit must all fail loudly on read — never silent garbage)."""
import json
import os
import struct
import threading

import numpy as np
import pytest

from repro.core import CEAZ, CEAZConfig
from repro.data import fields as F
from repro.io import engine as E


@pytest.fixture(scope="module")
def shards():
    return [F.nyx_proxy(seed=s) for s in range(4)]


def _write(path, shards, **kw):
    return E.write_stream(str(path), shards,
                          CEAZ(CEAZConfig(mode="rel", eb=1e-4,
                                          use_fused=True)),
                          fsync=False, **kw)


# -- ordered commit / byte identity -----------------------------------------

def test_async_byte_identical_to_sync(tmp_path, shards):
    """The whole point of ordered commit: overlap must not change a
    single byte of the stream. telemetry=False because the embedded
    manifest carries wall-clock timings (docs/OBSERVABILITY.md) —
    with it off the files must match bit for bit."""
    _write(tmp_path / "async.ceazs", shards, sync=False, telemetry=False)
    _write(tmp_path / "sync.ceazs", shards, sync=True, telemetry=False)
    a = (tmp_path / "async.ceazs").read_bytes()
    b = (tmp_path / "sync.ceazs").read_bytes()
    assert a == b


def test_grouping_does_not_change_bytes(tmp_path, shards):
    """Each shard keeps its own adaptive-coder stream, so the overlap
    grain (group size) must be payload-invariant."""
    _write(tmp_path / "g1.ceazs", shards, group=1, telemetry=False)
    _write(tmp_path / "g4.ceazs", shards, group=4, telemetry=False)
    assert (tmp_path / "g1.ceazs").read_bytes() \
        == (tmp_path / "g4.ceazs").read_bytes()


def test_round_trip_within_bound(tmp_path, shards):
    _write(tmp_path / "s.ceazs", shards)
    back = E.read_stream_arrays(str(tmp_path / "s.ceazs"))
    for a, b in zip(back, shards):
        eb = 1e-4 * (b.max() - b.min())
        assert np.abs(a - b).max() <= eb


def test_stats_account_stages(tmp_path, shards):
    st = _write(tmp_path / "s.ceazs", shards)
    assert st.n_records == len(shards)
    assert st.raw_bytes == sum(s.nbytes for s in shards)
    assert st.stored_bytes < st.raw_bytes
    assert st.wall_s > 0 and st.compress_s > 0 and st.write_s > 0


# -- crash safety of the read side ------------------------------------------

def _good_stream(tmp_path):
    path = str(tmp_path / "good.ceazs")
    w = E.StreamWriter(path, fsync=False)
    for i, payload in enumerate([b"alpha" * 40, b"bravo" * 55,
                                 b"charlie" * 33]):
        w.append(f"k{i}", payload, {"codec": "raw"})
    w.close()
    return path


def test_truncated_file_fails_loudly(tmp_path):
    path = _good_stream(tmp_path)
    data = open(path, "rb").read()
    for cut in (10, len(data) // 2, len(data) - 7):
        with open(path, "wb") as f:
            f.write(data[:cut])
        with pytest.raises(E.StreamCorruptionError):
            E.StreamReader(path)


def test_corrupted_footer_checksum_fails_loudly(tmp_path):
    path = _good_stream(tmp_path)
    data = bytearray(open(path, "rb").read())
    foot_off, foot_len, _, _ = E.TRAILER.unpack(data[-E.TRAILER.size:])
    data[foot_off + foot_len // 2] ^= 0xFF      # flip a byte inside footer
    open(path, "wb").write(bytes(data))
    with pytest.raises(E.StreamCorruptionError, match="footer checksum"):
        E.StreamReader(path)


def test_corrupted_payload_fails_loudly(tmp_path):
    path = _good_stream(tmp_path)
    r = E.StreamReader(path)
    off = r.records[1]["offset"] + E.RECORD_HEADER.size + 3
    r.close()
    data = bytearray(open(path, "rb").read())
    data[off] ^= 0xFF
    open(path, "wb").write(bytes(data))
    r = E.StreamReader(path)                    # index itself is intact
    with pytest.raises(E.StreamCorruptionError, match="checksum"):
        r.payload(1)


def test_out_of_order_commit_fails_loudly(tmp_path):
    """Each payload block self-identifies with its seq; a committer that
    swapped two shards is caught even when the index looks sane."""
    path = _good_stream(tmp_path)
    r = E.StreamReader(path)
    off0, off1 = r.records[0]["offset"], r.records[1]["offset"]
    r.close()
    data = bytearray(open(path, "rb").read())
    # rewrite the embedded seq fields as an out-of-order committer would
    # have: record slot 0 holds shard 1's block and vice versa
    struct.pack_into("<I", data, off0 + 4, 1)
    struct.pack_into("<I", data, off1 + 4, 0)
    open(path, "wb").write(bytes(data))
    r = E.StreamReader(path)
    with pytest.raises(E.StreamCorruptionError, match="out-of-order"):
        r.payload(0)


def test_index_seq_permutation_fails_at_open(tmp_path):
    path = _good_stream(tmp_path)
    r = E.StreamReader(path)
    foot_off = r.records[-1]["offset"] + E.RECORD_HEADER.size \
        + r.records[-1]["nbytes"]
    r.close()
    import json
    import zlib
    data = bytearray(open(path, "rb").read())
    _, foot_len, _, _ = E.TRAILER.unpack(data[-E.TRAILER.size:])
    doc = json.loads(bytes(data[foot_off:foot_off + foot_len]))
    doc["records"][0], doc["records"][1] = (doc["records"][1],
                                            doc["records"][0])
    footer = json.dumps(doc, sort_keys=True,
                        separators=(",", ":")).encode()
    data = data[:foot_off] + footer + E.TRAILER.pack(
        foot_off, len(footer), zlib.crc32(footer) & 0xFFFFFFFF,
        E.END_MAGIC)
    open(path, "wb").write(bytes(data))
    with pytest.raises(E.StreamCorruptionError, match="out-of-order"):
        E.StreamReader(path)


# -- engine failure + backpressure behavior ----------------------------------

def test_compress_error_propagates_and_no_file(tmp_path):
    path = str(tmp_path / "boom.ceazs")

    def bad_compress(keys, items):
        raise ValueError("compressor exploded")

    eng = E.AsyncCompressWriteEngine(path, bad_compress, fsync=False)
    eng.submit("a", np.zeros(8, np.float32))
    with pytest.raises(RuntimeError, match="compressor exploded"):
        # either submit or close surfaces it, depending on timing
        for _ in range(64):
            eng.submit("b", np.zeros(8, np.float32))
        eng.close()
    assert not os.path.exists(path)             # never finalized


def test_backpressure_bounds_inflight(tmp_path):
    """A slow committer must stall compression at max_inflight, not let
    it run ahead of storage unboundedly."""
    inflight, peak = [0], [0]
    lock = threading.Lock()

    def compress(keys, items):
        with lock:
            inflight[0] += 1
            peak[0] = max(peak[0], inflight[0])
        return [np.asarray(i).tobytes() for i in items]

    def slow_serialize(obj):
        import time
        time.sleep(0.02)
        with lock:
            inflight[0] -= 1
        return obj, {"codec": "raw"}

    eng = E.AsyncCompressWriteEngine(
        str(tmp_path / "bp.ceazs"), compress, slow_serialize,
        max_inflight=2, writers=1, fsync=False)
    with eng:
        for i in range(16):
            eng.submit(f"k{i}", np.full(4, i, np.float32))
    # compress runs ahead of the slow writer by at most the two bounded
    # queues plus the item in flight
    assert peak[0] <= 2 * 2 + 1, peak[0]
    assert len(E.StreamReader(str(tmp_path / "bp.ceazs"))) == 16


# -- read side: prefetch -> device-decode pipeline ---------------------------

def test_read_pipeline_matches_sync(tmp_path, shards):
    """Prefetch + batched fused decode must yield the same records, in
    commit order, as the inline sync read."""
    path = str(tmp_path / "s.ceazs")
    _write(path, shards)
    a = E.read_stream_arrays(path)
    b = E.read_stream_arrays(path, sync=True)
    assert len(a) == len(b) == len(shards)
    for x, y, s in zip(a, b, shards):
        assert np.array_equal(x, y)
        assert np.abs(x - s).max() <= 1e-4 * (s.max() - s.min())


def test_read_pipeline_group_invariance(tmp_path, shards):
    """The decode-batch grain must not change any decoded value."""
    path = str(tmp_path / "s.ceazs")
    _write(path, shards)
    for g in (1, 3, 16):
        for x, y in zip(E.read_stream_arrays(path, group=g),
                        E.read_stream_arrays(path, group=2)):
            assert np.array_equal(x, y)


def test_read_pipeline_stats(tmp_path, shards):
    path = str(tmp_path / "s.ceazs")
    _write(path, shards)
    with E.AsyncDecodeReadEngine(path) as eng:
        assert len(eng) == len(shards)
        out = eng.objects()
    assert len(out) == len(shards)
    st = eng.stats
    assert st.n_records == len(shards)
    assert st.raw_bytes == sum(s.nbytes for s in shards)
    assert st.stored_bytes < st.raw_bytes
    assert st.wall_s > 0 and st.read_s > 0 and st.decode_s > 0


def test_read_pipeline_surfaces_corruption(tmp_path, shards):
    """Payload corruption must propagate out of the prefetch thread as
    StreamCorruptionError on the consuming side — never silent garbage."""
    path = str(tmp_path / "s.ceazs")
    _write(path, shards)
    r = E.StreamReader(path)
    off = r.records[2]["offset"] + E.RECORD_HEADER.size + 5
    r.close()
    data = bytearray(open(path, "rb").read())
    data[off] ^= 0xFF
    open(path, "wb").write(bytes(data))
    with pytest.raises(E.StreamCorruptionError, match="checksum"):
        E.read_stream_arrays(path)


def test_read_seq_random_access(tmp_path, shards):
    """Satellite: the footer index gives O(1) record access — restore
    can fetch one leaf without scanning the stream."""
    path = str(tmp_path / "s.ceazs")
    _write(path, shards)
    from repro.core import CEAZ
    comp = CEAZ(CEAZConfig(use_fused=True))
    with E.StreamReader(path) as r:
        obj = r.read_seq(2)                       # one seek+read
        rec = comp.decompress(obj)
        assert np.abs(rec - shards[2]).max() \
            <= 1e-4 * (shards[2].max() - shards[2].min())
        assert r.seq_of(r.records[1]["key"]) == 1
        by_key = r.read_key(r.records[1]["key"])
        assert np.array_equal(comp.decompress(by_key),
                              comp.decompress(r.read_seq(1)))
        with pytest.raises(IndexError):
            r.read_seq(len(shards))
        with pytest.raises(KeyError):
            r.seq_of("no_such_key")


def test_random_access_out_of_range_and_missing_key(tmp_path):
    """Satellite: paging makes seq/key lookups the hot path — the edges
    must fail with clean, typed errors, not silent wraparound (negative
    seqs index from the end in plain lists) or chained internals."""
    path = _good_stream(tmp_path)
    with E.StreamReader(path) as r:
        for bad in (-1, len(r), len(r) + 7):
            with pytest.raises(IndexError, match="out of range"):
                r.read_seq(bad)
        with pytest.raises(KeyError) as ei:
            r.seq_of("no_such_key")
        # `raise ... from None`: the internal dict miss is suppressed,
        # the user-facing KeyError is the whole story
        assert ei.value.__suppress_context__
        assert "no_such_key" in str(ei.value)
        with pytest.raises(KeyError):
            r.read_key("no_such_key")


def test_duplicate_record_key_fails_at_open(tmp_path):
    """Satellite bugfix fence: duplicate keys used to silently map to
    the LAST record via dict-comprehension overwrite — key-addressed
    reads (the paging layer) would shadow a record. The format requires
    unique keys; the reader must refuse the stream at open."""
    path = str(tmp_path / "dup.ceazs")
    w = E.StreamWriter(path, fsync=False)
    w.append("k", b"alpha" * 8, {"codec": "raw"})
    w.append("unique", b"bravo" * 8, {"codec": "raw"})
    w.append("k", b"charlie" * 8, {"codec": "raw"})
    w.close()
    with pytest.raises(E.StreamCorruptionError,
                       match="duplicate record key"):
        E.StreamReader(path)


def test_footer_index_truncation_fails_at_open(tmp_path):
    """Cuts inside the footer index or trailer (the random-access
    lookup structures) must be caught by open-time validation."""
    path = _good_stream(tmp_path)
    data = open(path, "rb").read()
    foot_off, foot_len, _, _ = E.TRAILER.unpack(data[-E.TRAILER.size:])
    for cut in (foot_off,                         # index gone entirely
                foot_off + foot_len // 2,         # mid-index
                len(data) - E.TRAILER.size // 2):  # mid-trailer
        open(path, "wb").write(data[:cut])
        with pytest.raises(E.StreamCorruptionError):
            E.StreamReader(path)


def test_read_engine_abandoned_close_is_prompt(tmp_path, shards):
    """Closing without draining must not stall: the prefetch thread's
    sentinel put backs off when the consumer goes away."""
    import time
    path = str(tmp_path / "s.ceazs")
    _write(path, shards)
    eng = E.AsyncDecodeReadEngine(path, group=1, max_inflight=1)
    time.sleep(0.2)                 # let the prefetcher fill the queue
    t0 = time.perf_counter()
    eng.close()                     # nothing consumed
    assert time.perf_counter() - t0 < 2.0


def test_read_engine_is_one_shot(tmp_path, shards):
    """Re-iterating a drained engine must fail loudly, not hang on the
    empty queue."""
    path = str(tmp_path / "s.ceazs")
    _write(path, shards)
    with E.AsyncDecodeReadEngine(path) as eng:
        assert len(eng.objects()) == len(shards)
        with pytest.raises(RuntimeError, match="one-shot"):
            list(eng)


def test_stream_records_block_size_and_reader_uses_it(tmp_path, shards):
    """Decode needs the encoder's block grain: the writer records it in
    the footer meta, the default reader picks it up, and a forced
    mismatch raises instead of silently decoding garbage."""
    path = str(tmp_path / "bs.ceazs")
    comp = CEAZ(CEAZConfig(mode="rel", eb=1e-4, use_fused=True,
                           block_size=1024))
    E.write_stream(path, shards, comp, fsync=False)
    with E.StreamReader(path) as r:
        assert r.meta["block_size"] == 1024
    back = E.read_stream_arrays(path)           # self-configured reader
    for a, b in zip(back, shards):
        assert np.abs(a - b).max() <= 1e-4 * (b.max() - b.min())
    bad = CEAZ(CEAZConfig(mode="rel", eb=1e-4, use_fused=True,
                          block_size=4096))
    with pytest.raises(ValueError, match="block_size"):
        E.read_stream_arrays(path, bad)


def test_legacy_footer_without_block_size_warns_and_decodes(tmp_path,
                                                           shards):
    """Regression: streams from pre-block-grain writers (footer meta has
    no 'block_size') must decode through the self-configuring reader by
    falling back to the config default WITH a warning — not KeyError,
    not a silent guess."""
    path = str(tmp_path / "legacy.ceazs")
    comp = CEAZ(CEAZConfig(mode="rel", eb=1e-4, use_fused=True))
    # legacy writer: engine constructed without block_size, so the
    # footer meta carries no grain (exactly what pre-PR-3 writers wrote)
    eng = E.AsyncCompressWriteEngine(
        path, E.ceaz_compress_fn(comp), fsync=False)
    with eng:
        for i, s in enumerate(shards):
            eng.submit(f"shard_{i}", s)
    with E.StreamReader(path) as r:
        assert "block_size" not in r.meta
    with pytest.warns(UserWarning, match="block_size"):
        back = E.read_stream_arrays(path)
    for a, b in zip(back, shards):
        assert np.abs(a - b).max() <= 1e-4 * (b.max() - b.min())
    # an explicitly configured reader stays warning-free
    back2 = E.read_stream_arrays(path, comp)
    for a, b in zip(back2, back):
        assert np.array_equal(a, b)


# -- consumers ---------------------------------------------------------------

def test_parallel_read_self_configures_block_size(tmp_path, shards):
    """A dump written with a non-default block grain reads back through
    the default parallel_read: the footer meta carries the grain."""
    from repro.io.filewrite import parallel_compressed_write, parallel_read
    comp = CEAZ(CEAZConfig(mode="rel", eb=1e-4, use_fused=True,
                           block_size=1024))
    parallel_compressed_write(str(tmp_path), shards, comp=comp,
                              fsync=False)
    back = parallel_read(str(tmp_path))
    for a, b in zip(back, shards):
        assert np.abs(a - b).max() <= 1e-4 * (b.max() - b.min())


def test_gather_stream_round_trip(tmp_path):
    from repro.io.collectives import ceaz_gather_stream
    shards = [F.nyx_proxy(seed=s) for s in range(3)]
    stats = ceaz_gather_stream(shards, str(tmp_path / "g.ceazs"))
    assert stats["n_ranks"] == 3
    assert stats["ratio"] > 3.0
    back = E.read_stream_arrays(str(tmp_path / "g.ceazs"))
    for a, b in zip(back, shards):
        assert np.abs(a - b).max() <= 1e-4 * (b.max() - b.min())


def test_grad_snapshot_stream_round_trip(tmp_path):
    from repro.optim.grad_compress import (restore_grad_snapshot_stream,
                                           snapshot_grads_to_stream)
    rng = np.random.default_rng(0)
    grads = {"w": F.nyx_proxy(seed=1),
             "b": rng.standard_normal(16).astype(np.float32),
             "step": np.int32(7)}
    path = str(tmp_path / "snap.ceazs")
    stats = snapshot_grads_to_stream(path, grads, eb_rel=1e-3)
    assert stats["n_records"] == 3
    back = restore_grad_snapshot_stream(path)
    w = grads["w"]
    assert np.abs(back["w"] - w).max() <= 1e-3 * (w.max() - w.min())
    assert np.array_equal(back["b"], grads["b"])        # small leaf raw
    assert back["step"] == 7


def test_compress_batch_fused_float64_and_value_direct(tmp_path):
    """Satellite regression: float64 / predictor='none' inputs flow
    through the facade's fused grouping — no caller split, and since
    PR 5 no staged fallback either (they decode fused too)."""
    rng = np.random.default_rng(3)
    x64 = np.cumsum(rng.standard_normal((64, 256))).reshape(64, 256)
    comp = CEAZ(CEAZConfig(mode="rel", eb=1e-5, use_fused=True))
    outs = comp.compress_batch([x64, x64 * 2.0])
    assert all(c.word_bits == 64 for c in outs)         # float64 streams
    for c, x in zip(outs, [x64, x64 * 2.0]):
        rec = comp.decompress(c)
        assert np.abs(rec - x).max() <= 1e-5 * (x.max() - x.min())

    direct = CEAZ(CEAZConfig(mode="rel", eb=1e-4, predictor="none",
                             use_fused=True))
    noise = rng.standard_normal(20000).astype(np.float32)
    (c,) = direct.compress_batch([noise])
    assert c.predictor == "none"                        # value-direct path
    rec = direct.decompress(c)
    assert np.abs(rec - noise).max() <= 1e-4 * (noise.max() - noise.min())


# -- stream fuzzing: corruption sweep over the STREAM_FORMAT.md layout -------

def _ceaz_stream(tmp_path):
    """A real .ceazs stream whose payloads are pickled CEAZCompressed
    records with SHIPPED CODEBOOKS (adaptive=False rebuilds per chunk,
    so every chunk carries its lengths array — the fuzz target)."""
    path = str(tmp_path / "fuzz.ceazs")
    rng = np.random.default_rng(4)
    shards = [np.cumsum(rng.standard_normal(6000)).astype(np.float32)
              for _ in range(3)]
    comp = CEAZ(CEAZConfig(mode="rel", eb=1e-4, use_fused=True,
                           adaptive=False, chunk_bytes=1 << 13))
    E.write_stream(path, shards, comp, fsync=False)
    return path, shards, comp


def test_fuzz_bit_flip_in_codebook_bytes(tmp_path):
    """Flip bits INSIDE a record's serialized codebook lengths: the
    payload CRC must catch it — never a silently wrong codebook."""
    path, shards, comp = _ceaz_stream(tmp_path)
    r = E.StreamReader(path)
    rec = r.records[1]
    payload = r.payload(1)
    c = E.deserialize_payload(payload, rec)
    lengths = c.chunks[0].codebook_lengths
    assert lengths is not None
    needle = lengths.tobytes()
    pos = payload.find(needle)
    assert pos > 0                       # the codebook bytes are locatable
    base = rec["offset"] + E.RECORD_HEADER.size
    r.close()
    data = bytearray(open(path, "rb").read())
    for bit in (0, 3, 7):                # sweep bits across the lengths area
        fuzzed = bytearray(data)
        fuzzed[base + pos + bit * 11] ^= 1 << bit
        open(path, "wb").write(bytes(fuzzed))
        rr = E.StreamReader(path)        # index itself is intact
        with pytest.raises(E.StreamCorruptionError, match="checksum"):
            rr.payload(1)
        rr.close()
    open(path, "wb").write(bytes(data))  # restore: stream reads clean again
    assert len(E.read_stream_arrays(path)) == len(shards)


def _section_boundaries(path):
    """Every section boundary of the v1 layout (STREAM_FORMAT.md): after
    the stream magic, after each record header, after each payload,
    footer start/middle, trailer start, before the end magic."""
    r = E.StreamReader(path)
    records = list(r.records)
    r.close()
    data = open(path, "rb").read()
    foot_off, foot_len, _, _ = E.TRAILER.unpack(data[-E.TRAILER.size:])
    cuts = {len(E.STREAM_MAGIC)}
    for rec in records:
        cuts.add(rec["offset"] + E.RECORD_HEADER.size)          # after header
        cuts.add(rec["offset"] + E.RECORD_HEADER.size + rec["nbytes"])
    cuts.add(foot_off)                                          # footer start
    cuts.add(foot_off + foot_len // 2)                          # mid-footer
    cuts.add(foot_off + foot_len)                               # trailer start
    cuts.add(len(data) - len(E.END_MAGIC))                      # pre end-magic
    return sorted(c for c in cuts if c < len(data)), data


def test_fuzz_truncation_at_every_section_boundary(tmp_path):
    """Truncating the stream at ANY section boundary must raise
    StreamCorruptionError at open or payload access — never return
    garbage arrays."""
    path, shards, comp = _ceaz_stream(tmp_path)
    cuts, data = _section_boundaries(path)
    assert len(cuts) >= 10               # all sections of the 3-record file
    for cut in cuts:
        open(path, "wb").write(data[:cut])
        with pytest.raises(E.StreamCorruptionError):
            r = E.StreamReader(path)
            try:
                for i in range(len(r.records)):
                    E.deserialize_payload(r.payload(i), r.records[i])
            finally:
                r.close()
    open(path, "wb").write(data)
    back = E.read_stream_arrays(path)
    for a, b in zip(back, shards):
        assert np.abs(a - b).max() <= 1e-4 * (b.max() - b.min())


# -- decode differential-fuzz fence: staged / megakernel agree ---------------
#
# A corrupted stream must be judged IDENTICALLY by both decode routes,
# the staged host loop and the fused ceaz_chunk_dec megakernel — the
# fence that keeps a route from silently decoding garbage the other
# rejects.

_CORPUS = os.path.join(os.path.dirname(__file__), "corpus",
                       "decode_fuzz_corpus.json")


def _decode_impl_comps():
    """The two decode routes every corrupted stream must judge
    identically: staged (per-chunk host loop) and the fused decode
    megakernel."""
    base = dict(mode="rel", eb=1e-4, adaptive=False, chunk_bytes=1 << 13)
    return [
        ("staged", CEAZ(CEAZConfig(use_fused=False, **base))),
        ("mega", CEAZ(CEAZConfig(use_fused=True, **base))),
    ]


def _decode_verdicts(path):
    """(impl, 'ok'|'corrupt', decoded-bytes) per decode route. Anything
    other than a clean decode or StreamCorruptionError escapes — a
    route crashing differently than the others IS a fence failure."""
    out = []
    for name, comp in _decode_impl_comps():
        try:
            arrs = E.read_stream_arrays(path, comp, sync=True)
            out.append((name, "ok", tuple(a.tobytes() for a in arrs)))
        except E.StreamCorruptionError:
            out.append((name, "corrupt", None))
    return out


def _apply_corpus_case(data, records, case):
    """One corpus entry -> mutated stream bytes (record-relative offsets
    keep the corpus valid across encoder byte-layout drift)."""
    if case["kind"] == "truncate_index":
        # cuts inside the footer index / trailer — positions computed
        # from the live trailer so the corpus survives layout drift
        foot_off, foot_len, _, _ = E.TRAILER.unpack(
            data[-E.TRAILER.size:])
        cut = {"mid_footer": foot_off + foot_len // 2,
               "mid_trailer": len(data) - E.TRAILER.size // 2}[case["at"]]
        return data[:cut]
    rec = records[case["record"] % len(records)]
    body = rec["offset"] + E.RECORD_HEADER.size
    if case["kind"] == "bitflip":
        mut = bytearray(data)
        mut[body + case["rel_off"] % rec["nbytes"]] ^= 1 << (case["bit"] & 7)
        return bytes(mut)
    assert case["kind"] == "truncate"
    cut = {"after_header": body,
           "mid_payload": body + rec["nbytes"] // 2,
           "after_payload": body + rec["nbytes"]}[case["at"]]
    return data[:cut]


def test_decode_differential_fuzz_fence(tmp_path):
    """Seed corpus + derandomized random flips: every mutation must get
    the SAME verdict ('corrupt', here — payload CRCs catch all of these)
    from staged and megakernel decode, and the pristine stream must
    decode byte-identically through both."""
    path, shards, comp = _ceaz_stream(tmp_path)
    with E.StreamReader(path) as r:
        records = list(r.records)
    data = open(path, "rb").read()

    clean = _decode_verdicts(path)
    assert all(v == "ok" for _, v, _ in clean), clean
    assert len({b for _, _, b in clean}) == 1          # byte-identical

    corpus = json.load(open(_CORPUS))
    cases = list(corpus["cases"])
    rng = np.random.default_rng(corpus["random"]["seed"])
    for _ in range(corpus["random"]["n_bitflips"]):
        cases.append({"kind": "bitflip",
                      "record": int(rng.integers(len(records))),
                      "rel_off": int(rng.integers(1 << 16)),
                      "bit": int(rng.integers(8))})
    for case in cases:
        open(path, "wb").write(_apply_corpus_case(data, records, case))
        verdicts = _decode_verdicts(path)
        assert len({(v, b) for _, v, b in verdicts}) == 1, (case, verdicts)
        assert verdicts[0][1] == "corrupt", (case, verdicts)
    open(path, "wb").write(data)               # restore: reads clean again
    assert len(E.read_stream_arrays(path)) == len(shards)


def test_megakernel_decode_terminates_on_garbage_bits():
    """The megakernel walk is a fori bounded by min(count, block_size)
    with every cursor clamped into the words window — fully random
    words/tables/nbits (including zero-length table entries that never
    advance the cursor) must return a well-shaped array in finite time
    from BOTH the jnp twin and the Pallas interpreter, in both the fused
    and word-tiled regimes. Decoded values on garbage are unspecified
    (stream CRCs reject corrupted payloads before decode runs)."""
    from repro.kernels.megakernel import decode_kernel as DK
    from repro.kernels.megakernel import ops as MO
    from repro.kernels.megakernel import ref as MR
    g = json.load(open(_CORPUS))["garbage"]
    rng = np.random.default_rng(g["seed"])
    shapes = [(int(rng.integers(1, 4)), int(rng.integers(1, 7)), 32)
              for _ in range(g["cases"])]
    shapes.append((1, DK._DEC_FUSE_LIMIT // 256 + 8, 256))  # tiled regime
    for C, NB, bs in shapes:
        W = int(rng.integers(3, 24))
        args = (rng.integers(0, 1 << 32, size=(C, W), dtype=np.uint32),
                rng.integers(0, 1 << 12, size=(C, NB)).astype(np.int32),
                rng.integers(0, NB * bs + 1, size=C).astype(np.int32),
                rng.integers(0, 1024, size=(1 << 16,)).astype(np.uint16),
                rng.integers(0, 17, size=(1 << 16,)).astype(np.uint8),
                np.zeros(C, np.int32),
                rng.integers(-999, 999, size=(C, 4)).astype(np.int32),
                rng.integers(-5, 6, size=C).astype(np.int32),
                np.zeros(C, np.int32),
                rng.integers(0, 2, size=C).astype(np.int32))
        for q in (np.asarray(MR.ceaz_chunk_dec(*args, block_size=bs)),
                  np.asarray(MO.ceaz_chunk_dec(*args, block_size=bs,
                                               interpret=True))):
            assert q.shape == (C, NB * bs)
            assert q.dtype == np.int32


def test_group_decode_failure_names_the_record(tmp_path):
    """Satellite regression: a failure inside the batched decode pass
    must name WHICH record failed — the engine replays the group one
    record at a time and re-raises tagged with `record seq=...` (the
    original exception type intact, the group error chained)."""
    path = str(tmp_path / "named.ceazs")
    rng = np.random.default_rng(7)
    shards = [np.cumsum(rng.standard_normal(n)).astype(np.float32)
              for n in (5000, 7777, 6000)]
    comp = CEAZ(CEAZConfig(mode="rel", eb=1e-4, use_fused=True))
    E.write_stream(path, shards, comp, fsync=False)

    class PoisonedComp:
        """Stands in for a payload that deserializes fine but explodes
        in the device pass: the batched call fails opaquely; only the
        per-record replay can pinpoint the 7777-value record."""

        def decompress_batch(self, objs):
            if any(int(o.n_values) == 7777 for o in objs):
                raise ValueError("device pass exploded")
            return comp.decompress_batch(objs)

    with pytest.raises(ValueError, match=r"record seq=1\b") as ei:
        E.read_stream_arrays(path, PoisonedComp(), group=8, sync=True)
    assert ei.value.__cause__ is not None      # group failure chained


# -- telemetry satellites: wall_s terminal-state + footer forward-compat -----

def test_write_engine_wall_s_set_once_on_error_path(tmp_path):
    """Regression: wall_s is stamped exactly once, at the terminal state
    — a failing close must still leave a final wall clock, and reading
    stats repeatedly must not change it."""
    path = str(tmp_path / "werr.ceazs")

    def bad_compress(keys, items):
        raise ValueError("compressor exploded")

    eng = E.AsyncCompressWriteEngine(path, bad_compress, fsync=False)
    eng.submit("a", np.zeros(8, np.float32))
    with pytest.raises(RuntimeError, match="compressor exploded"):
        for _ in range(64):
            eng.submit("b", np.zeros(8, np.float32))
        eng.close()
    w = eng.stats.wall_s
    assert w > 0
    assert eng.stats.wall_s == w            # stable across reads
    eng.abort()                             # later abort must not clobber
    assert eng.stats.wall_s == w


def test_write_engine_wall_s_idempotent_on_close(tmp_path):
    path = str(tmp_path / "wok.ceazs")

    def compress(keys, items):
        return [np.asarray(i).tobytes() for i in items]

    eng = E.AsyncCompressWriteEngine(path, compress, fsync=False)
    eng.submit("a", np.zeros(8, np.float32))
    st = eng.close()
    w = st.wall_s
    assert w > 0
    eng.close()                             # second close: no re-stamp
    assert eng.stats.wall_s == w


def test_read_engine_wall_s_set_on_error_path(tmp_path, shards):
    path = str(tmp_path / "rerr.ceazs")
    _write(path, shards)
    r = E.StreamReader(path)
    off = r.records[1]["offset"] + E.RECORD_HEADER.size + 5
    r.close()
    data = bytearray(open(path, "rb").read())
    data[off] ^= 0xFF
    open(path, "wb").write(bytes(data))
    eng = E.AsyncDecodeReadEngine(path)
    with pytest.raises(E.StreamCorruptionError):
        eng.objects()
    eng.close()
    w = eng.stats.wall_s
    assert w > 0
    assert eng.stats.wall_s == w


def _rewrite_footer(path, mutate):
    """Rewrite the stream footer through `mutate(doc)` and restamp the
    trailer (length + crc) so only the JSON content differs."""
    import json
    import zlib
    r = E.StreamReader(path)
    foot_off = r.records[-1]["offset"] + E.RECORD_HEADER.size \
        + r.records[-1]["nbytes"]
    r.close()
    data = bytearray(open(path, "rb").read())
    _, foot_len, _, _ = E.TRAILER.unpack(data[-E.TRAILER.size:])
    doc = json.loads(bytes(data[foot_off:foot_off + foot_len]))
    mutate(doc)
    footer = json.dumps(doc, sort_keys=True, separators=(",", ":")).encode()
    data = data[:foot_off] + footer + E.TRAILER.pack(
        foot_off, len(footer), zlib.crc32(footer) & 0xFFFFFFFF, E.END_MAGIC)
    open(path, "wb").write(bytes(data))


def test_footer_unknown_meta_keys_are_ignored(tmp_path, shards):
    """Forward compat: a reader from this version must open streams whose
    footer meta carries keys it has never heard of — a future telemetry
    schema, brand-new meta entries, even unknown top-level doc keys. The
    `telemetry` key is advisory, never load-bearing
    (docs/STREAM_FORMAT.md)."""
    path = str(tmp_path / "future.ceazs")
    _write(path, shards)
    want = E.read_stream_arrays(path)

    future_manifest = {"schema": 999, "hyperdrive": {"warp": [9, 9, 9]},
                       "stages": "reshaped-beyond-recognition"}

    def mutate(doc):
        doc["meta"]["telemetry"] = future_manifest
        doc["meta"]["from_the_future"] = {"nested": ["junk", 42]}
        doc["not_a_known_top_level_key"] = True

    _rewrite_footer(path, mutate)
    r = E.StreamReader(path)                 # must NOT raise
    try:
        # unknown meta is preserved verbatim, telemetry() hands it back
        # as-is without interpreting it
        assert r.meta["from_the_future"] == {"nested": ["junk", 42]}
        assert r.telemetry() == future_manifest
    finally:
        r.close()
    got = E.read_stream_arrays(path)         # payloads fully readable
    for a, b in zip(got, want):
        assert np.array_equal(a, b)
