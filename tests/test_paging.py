"""Decode-on-demand parameter paging + hot swap (repro/serve/paging.py).

Fences the PR's acceptance criteria:
  * paged reads are BIT-identical to the full `restore_serving_params`
    restore for every leaf (same sharding, same bytes),
  * the decoded-layer LRU respects its byte budget under random access,
  * hot swap under concurrent page reads never yields a
    mixed-generation tree,
  * the fused serving-dtype cast (satellite bugfix) matches the old
    cast-after-restore semantics leaf for leaf.
"""
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import ckpt as C
from repro.launch import serve as S
from repro.obs import metrics as om
from repro.runtime.sharding import ShardingPlan, make_plan
from repro.serve.paging import PagedParamStore

PLAN = ShardingPlan(mesh=None)


def _state(seed=0, shift=0.0):
    """A small tree with PARAM_RULES-shaped keys; every float leaf is
    big enough (>= min_compress) to ride the ceaz codec except `norm`
    (raw npy) — both checkpoint paths are exercised."""
    rng = np.random.default_rng(seed)
    mk = lambda *s: (rng.standard_normal(s) + shift).astype(np.float32)
    return {"params": {"embed": {"table": mk(512, 64)},
                       "layers": [{"mlp": {"wi": mk(64, 128),
                                           "wo": mk(128, 64)}}
                                  for _ in range(4)],
                       "norm": np.ones((64,), np.float32) + shift},
            "step": np.int32(1)}


def _save(tmp_path, step, **kw):
    d = str(tmp_path / "ckpt")
    C.save_checkpoint(d, _state(**kw), step)
    return d


def _flat(tree):
    return {"/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                     for k in path): leaf
            for path, leaf in
            jax.tree_util.tree_flatten_with_path(tree)[0]}


def _ckpt_comp():
    return C._compressor(C.CheckpointConfig())


# -- bit identity with the full restore --------------------------------------

def test_paged_bit_identical_to_full_restore(tmp_path):
    d = _save(tmp_path, 3)
    params, meta = S.restore_serving_params(d, PLAN)
    store, meta2 = S.restore_serving_params(d, PLAN, paged=True)
    assert meta2["step"] == meta["step"]
    with store:
        with store.pin() as pin:
            paged = pin.params()
        ff, fp = _flat(params), _flat(paged)
        assert set(ff) == set(fp)
        for k in ff:
            a, b = np.asarray(ff[k]), np.asarray(fp[k])
            assert a.dtype == b.dtype, k
            assert a.shape == b.shape, k
            assert a.tobytes() == b.tobytes(), \
                f"leaf {k} differs between paged and full restore"


def test_paged_placement_matches_full_restore_on_mesh(tmp_path):
    """Same PARAM_RULES sharding whether a leaf arrives via the paged
    path or the full restore (1-device mesh: placement logic identical,
    runs anywhere)."""
    mesh = jax.sharding.Mesh(
        np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    plan = make_plan(mesh)
    d = _save(tmp_path, 3)
    params, _ = S.restore_serving_params(d, plan)
    store, _ = S.restore_serving_params(d, plan, paged=True)
    with store, store.pin() as pin:
        ff, fp = _flat(params), _flat(pin.params())
        for k in ff:
            a, b = ff[k], fp[k]
            assert a.sharding.is_equivalent_to(b.sharding, a.ndim), k
            assert np.asarray(a).tobytes() == np.asarray(b).tobytes(), k


def test_fused_serving_cast_is_bf16_and_unchanged_semantics(tmp_path):
    """Satellite bugfix: the cast now happens per leaf BEFORE placement
    (peak = bf16 footprint) — the result must still be exactly
    astype(bf16) of the restored f32 leaves, ints untouched."""
    d = _save(tmp_path, 3)
    state, _ = C.restore_checkpoint(d, plan=PLAN)
    params, _ = S.restore_serving_params(d, PLAN)
    ff, fr = _flat(params), _flat(state["params"])
    for k, leaf in ff.items():
        assert leaf.dtype == (jnp.bfloat16 if np.issubdtype(
            np.asarray(fr[k]).dtype, np.floating)
            else np.asarray(fr[k]).dtype), k
        ref = np.asarray(fr[k])
        if np.issubdtype(ref.dtype, np.floating):
            ref = ref.astype(np.dtype(jnp.bfloat16))
        assert np.asarray(leaf).tobytes() == ref.tobytes(), k


# -- LRU budget ---------------------------------------------------------------

def test_lru_respects_byte_budget_under_random_access(tmp_path):
    d = _save(tmp_path, 3)
    stream = os.path.join(d, "step_00000003", C.LEAVES_STREAM)
    # room for ~2 of the 8192-element bf16 mlp leaves
    budget = 40_000
    ev0 = om.DEFAULT.counter(om.PAGE_EVICTIONS).value()
    with PagedParamStore(stream, plan=PLAN, comp=_ckpt_comp(),
                         prefix="params/", cache_bytes=budget) as store:
        keys = [k for k in store.keys() if "mlp" in k]
        rng = np.random.default_rng(5)
        with store.pin() as pin:
            for k in rng.choice(keys, size=24):
                pin.get(str(k))
                assert store.cache_resident_bytes <= budget
        assert om.DEFAULT.counter(om.PAGE_EVICTIONS).value() > ev0
        assert 0 < store.cache_resident_bytes <= budget


def test_oversized_leaf_is_served_but_not_retained(tmp_path):
    """A leaf bigger than the whole budget must still decode and be
    handed out — the cache just refuses to retain it (strict budget)."""
    d = _save(tmp_path, 3)
    stream = os.path.join(d, "step_00000003", C.LEAVES_STREAM)
    with PagedParamStore(stream, plan=PLAN, comp=_ckpt_comp(),
                         prefix="params/", cache_bytes=100) as store:
        with store.pin() as pin:
            leaf = pin.get("params/embed/table")
        assert leaf.shape == (512, 64)
        assert store.cache_resident_bytes == 0


def test_page_counters_and_gauge(tmp_path):
    d = _save(tmp_path, 3)
    stream = os.path.join(d, "step_00000003", C.LEAVES_STREAM)
    h0 = om.DEFAULT.counter(om.PAGE_HITS).value()
    m0 = om.DEFAULT.counter(om.PAGE_MISSES).value()
    with PagedParamStore(stream, plan=PLAN, comp=_ckpt_comp(),
                         prefix="params/") as store:
        with store.pin() as pin:
            pin.get("params/norm")           # cold: miss
            pin.get("params/norm")           # warm: hit
        assert om.DEFAULT.counter(om.PAGE_MISSES).value() == m0 + 1
        assert om.DEFAULT.counter(om.PAGE_HITS).value() == h0 + 1
        assert om.DEFAULT.gauge(om.PAGE_CACHE_BYTES).value() \
            == store.cache_resident_bytes > 0


# -- hot swap -----------------------------------------------------------------

def _two_steps(tmp_path):
    d = str(tmp_path / "ckpt")
    C.save_checkpoint(d, _state(seed=0), 1)
    C.save_checkpoint(d, _state(seed=0, shift=3.0), 2)
    return (os.path.join(d, "step_00000001", C.LEAVES_STREAM),
            os.path.join(d, "step_00000002", C.LEAVES_STREAM))


def _truth(stream):
    """{record key: placed bytes} ground truth for one stream."""
    with PagedParamStore(stream, plan=PLAN, comp=_ckpt_comp(),
                         prefix="params/") as st, st.pin() as pin:
        return {k: np.asarray(v).tobytes()
                for k, v in pin.get_many(pin.keys()).items()}


def test_hot_swap_pins_never_see_mixed_generations(tmp_path):
    """Readers hammer pin->read-full-tree while swaps land mid-flight:
    every tree observed must be entirely generation A or entirely
    generation B bytes — one mixed leaf fails the fence."""
    s1, s2 = _two_steps(tmp_path)
    truth = [_truth(s1), _truth(s2)]
    assert truth[0] != truth[1]
    store = PagedParamStore(s1, plan=PLAN, comp=_ckpt_comp(),
                            prefix="params/", cache_bytes=60_000)
    stop = threading.Event()
    errors = []

    def reader():
        import random
        rnd = random.Random(threading.get_ident())
        while not stop.is_set():
            with store.pin() as pin:
                keys = pin.keys()
                rnd.shuffle(keys)
                got = {k: np.asarray(v).tobytes()
                       for k, v in pin.get_many(keys).items()}
            # every observed tree must be wholly one generation's bytes
            if not any(got == {k: t[k] for k in got} for t in truth):
                errors.append("mixed-generation read")
                stop.set()

    threads = [threading.Thread(target=reader) for _ in range(3)]
    for t in threads:
        t.start()
    try:
        for target in (s2, s1, s2):
            store.swap(target, comp=_ckpt_comp())
    finally:
        stop.set()
        for t in threads:
            t.join()
        store.close()
    assert not errors, errors


def test_pin_taken_before_swap_keeps_old_generation(tmp_path):
    s1, s2 = _two_steps(tmp_path)
    truth = [_truth(s1), _truth(s2)]
    store = PagedParamStore(s1, plan=PLAN, comp=_ckpt_comp(),
                            prefix="params/")
    old_pin = store.pin()
    gen0 = old_pin.generation
    gen1 = store.swap(s2, comp=_ckpt_comp())
    assert gen1 != gen0
    assert store.generation == gen1
    # the pre-swap pin still resolves every key against the old stream
    assert {k: np.asarray(v).tobytes()
            for k, v in old_pin.get_many(old_pin.keys()).items()} \
        == truth[0]
    with store.pin() as pin:
        assert {k: np.asarray(v).tobytes()
                for k, v in pin.get_many(pin.keys()).items()} == truth[1]
    # old generation stays alive only until its last pin releases
    assert store.n_generations == 2
    old_pin.release()
    assert store.n_generations == 1
    store.close()


def test_swap_to_corrupt_stream_leaves_store_serving(tmp_path):
    """A failed swap (new stream corrupt) must leave the current
    generation untouched and still serving."""
    import repro.io.engine as E
    s1, s2 = _two_steps(tmp_path)
    data = open(s2, "rb").read()
    open(s2, "wb").write(data[:len(data) // 2])
    store = PagedParamStore(s1, plan=PLAN, comp=_ckpt_comp(),
                            prefix="params/")
    gen0 = store.generation
    with pytest.raises(E.StreamCorruptionError):
        store.swap(s2, comp=_ckpt_comp())
    assert store.generation == gen0
    assert store.n_generations == 1
    with store.pin() as pin:
        assert pin.get("params/norm").shape == (64,)
    store.close()


def test_duplicate_key_stream_refused_for_paging(tmp_path):
    """The satellite bugfix seen from the paging layer: a stream with
    duplicate keys must be refused at store open, not silently served
    last-record-wins."""
    import repro.io.engine as E
    path = str(tmp_path / "dup.ceazs")
    w = E.StreamWriter(path, fsync=False)
    w.append("params/a", b"first", {"codec": "raw"})
    w.append("params/a", b"again", {"codec": "raw"})
    w.close()
    with pytest.raises(E.StreamCorruptionError, match="duplicate"):
        PagedParamStore(path, plan=PLAN, comp=_ckpt_comp())


def test_paged_open_non_corruption_error_propagates(tmp_path, monkeypatch):
    """Only corruption (OSError) reads as "no usable checkpoint"; any
    other failure while opening the paged store propagates."""
    import repro.serve.paging as PG
    d = _save(tmp_path, 3)

    def broken(self, *a, **kw):
        raise RuntimeError("device program failed to compile")

    monkeypatch.setattr(PG.PagedParamStore, "__init__", broken)
    with pytest.raises(RuntimeError, match="failed to compile"):
        S.paged_serving_store(d, PLAN)


def test_paged_open_corrupt_manifest_is_unusable(tmp_path):
    d = _save(tmp_path, 3)
    with open(os.path.join(d, "step_00000003", "manifest.json"), "w") as f:
        f.write("[]")
    assert S.paged_serving_store(d, PLAN) is None
