"""Multi-device semantics (subprocess with forced host devices):
TP/DP equivalence, compressed pod exchange, elastic restore, pipeline
parallelism, compressed gather collective."""
import textwrap

import pytest

from conftest import run_with_devices


@pytest.mark.slow
def test_tp_dp_matches_single_device():
    out = run_with_devices(textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_arch
        from repro.launch import mesh as M
        from repro.launch.train import (TrainConfig, init_state,
                                        jit_train_step, make_plan_for)
        from repro.data.synthetic import DataConfig, batch_for_step
        from repro.runtime.sharding import ShardingPlan
        cfg = get_arch('glm4-9b').reduced()
        dc = DataConfig(vocab_size=cfg.vocab_size, global_batch=4,
                        seq_len=32)
        tc = TrainConfig()
        losses = {}
        for name, mesh in (('single', None),
                           ('2x2', M.make_mesh((2, 2), ('data', 'model')))):
            plan = (make_plan_for(cfg, mesh) if mesh is not None
                    else ShardingPlan(mesh=None))
            state = init_state(jax.random.key(0), cfg, tc, plan)
            b = {k: jnp.asarray(v)
                 for k, v in batch_for_step(dc, 0).items()}
            fn = jit_train_step(cfg, tc, plan, state, b)
            ls = []
            for i in range(3):
                b = {k: jnp.asarray(v)
                     for k, v in batch_for_step(dc, i).items()}
                state, m = fn(state, b)
                ls.append(float(m['loss']))
            losses[name] = ls
        a, b = losses['single'], losses['2x2']
        assert all(abs(x - y) < 5e-2 for x, y in zip(a, b)), (a, b)
        print('TP/DP == single-device:', a, b)
    """), n_devices=4)
    assert "TP/DP == single-device" in out


@pytest.mark.slow
def test_compressed_pod_exchange_tracks_baseline():
    out = run_with_devices(textwrap.dedent("""
        import jax, jax.numpy as jnp
        from repro.configs import get_arch
        from repro.launch import mesh as M
        from repro.launch.train import (TrainConfig, init_state,
                                        jit_train_step, make_plan_for)
        from repro.data.synthetic import DataConfig, batch_for_step
        from repro.optim import CompressionConfig
        cfg = get_arch('glm4-9b').reduced()
        mesh = M.make_mesh((2, 2, 2), ('pod', 'data', 'model'))
        plan = make_plan_for(cfg, mesh)
        dc = DataConfig(vocab_size=cfg.vocab_size, global_batch=8,
                        seq_len=32)
        results = {}
        for on in (False, True):
            tc = TrainConfig(comp=CompressionConfig(bits=8, enabled=on))
            state = init_state(jax.random.key(0), cfg, tc, plan)
            b = {k: jnp.asarray(v) for k, v in batch_for_step(dc, 0).items()}
            fn = jit_train_step(cfg, tc, plan, state, b)
            ls = []
            for i in range(4):
                b = {k: jnp.asarray(v)
                     for k, v in batch_for_step(dc, i).items()}
                state, m = fn(state, b)
                ls.append(float(m['loss']))
            results[on] = ls
        base, comp = results[False], results[True]
        assert all(abs(x - y) < 0.05 for x, y in zip(base, comp)), \
            (base, comp)
        print('compressed-pod tracks baseline OK')
    """), n_devices=8)
    assert "tracks baseline OK" in out


@pytest.mark.slow
def test_elastic_restore_across_meshes():
    out = run_with_devices(textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np, tempfile
        from repro.checkpoint import ckpt as C
        from repro.configs import get_arch
        from repro.launch import mesh as M
        from repro.launch.train import TrainConfig, init_state, make_plan_for
        cfg = get_arch('glm4-9b').reduced()
        tc = TrainConfig()
        mesh4 = M.make_mesh((2, 2), ('data', 'model'))
        plan4 = make_plan_for(cfg, mesh4)
        state = init_state(jax.random.key(0), cfg, tc, plan4)
        d = tempfile.mkdtemp()
        C.save_checkpoint(d, state, step=1,
                          cfg=C.CheckpointConfig(mode='raw'))
        # restore onto a DIFFERENT mesh (node loss: 8 -> 2 devices)
        mesh2 = M.make_mesh((1, 2), ('data', 'model'))
        plan2 = make_plan_for(cfg, mesh2)
        restored, meta = C.restore_checkpoint(
            d, plan=plan2, cfg=C.CheckpointConfig(mode='raw'))
        for a, b in zip(jax.tree.leaves(state['params']),
                        jax.tree.leaves(restored['params'])):
            assert np.array_equal(np.asarray(a), np.asarray(b))
        shard = jax.tree.leaves(restored['params'])[0].sharding
        assert shard.mesh.shape['model'] == 2
        print('elastic restore OK')
    """), n_devices=8)
    assert "elastic restore OK" in out


@pytest.mark.slow
def test_pipeline_parallel_matches_sequential():
    out = run_with_devices(textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch import mesh as M
        from repro.runtime.pipeline import (pipeline_apply,
                                            sequential_reference)
        mesh = M.make_mesh((4,), ('stage',))
        def stage_fn(p, x):
            return jnp.tanh(x @ p['w'] + p['b'])
        k = jax.random.key(0)
        params = {'w': jax.random.normal(k, (4, 16, 16)) * 0.5,
                  'b': jnp.zeros((4, 16))}
        mbs = jax.random.normal(jax.random.key(1), (6, 8, 16))
        out = pipeline_apply(stage_fn, params, mbs, mesh, 'stage')
        ref = sequential_reference(stage_fn, params, mbs)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)
        print('pipeline == sequential OK')
    """), n_devices=4)
    assert "pipeline == sequential OK" in out


@pytest.mark.slow
def test_compressed_all_gather():
    out = run_with_devices(textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.io.collectives import compressed_all_gather, WireFormat
        from repro.launch import mesh as M
        mesh = M.make_mesh((4,), ('ranks',))
        x = jnp.asarray(np.cumsum(
            np.random.default_rng(0).standard_normal((4, 4096)),
            axis=1) / 50, jnp.float32)
        from jax.sharding import NamedSharding, PartitionSpec as P
        xs = jax.device_put(x, NamedSharding(mesh, P('ranks', None)))
        g = compressed_all_gather(xs, mesh, 'ranks',
                                  WireFormat(bits=8, use_lorenzo=True))
        g = np.asarray(g)
        for r in range(4):
            err = np.abs(g[r] - np.asarray(x)[r]).max()
            scale = np.abs(np.diff(np.asarray(x)[r])).max() / 127
            assert err <= scale * 4096 * 0.02 + 1e-3, (r, err)
        print('compressed all-gather OK')
    """), n_devices=4)
    assert "compressed all-gather OK" in out


# -- error-bounded gather of rank-sharded bricks ------------------------------
# One subprocess with 4 host devices compresses a 2x2 decomposition of a
# seeded 64x64x16 field (bricks of 32x32x16, 8 KiB chunks, so a brick
# spans eight chunks), once in distribution and once with brick 2
# replaced by sparse spikes (out of the bank's envelope), through
# `ceaz_gather_stream`; each test reads one part of what it reports.
GATHER_SCRIPT = textwrap.dedent("""
    import json, os, re, tempfile
    import jax, numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.core import CEAZ, CEAZConfig
    from repro.io import collectives, engine
    from repro.obs import metrics as om
    from repro.runtime import fused

    mesh = Mesh(np.array(jax.devices()[:4]), ('ranks',))
    cfg = dict(mode='rel', eb=1e-3, use_fused=True, chunk_bytes=1 << 13,
               block_size=1024)
    comp = CEAZ(CEAZConfig(codebook='bank', **cfg))
    exact = CEAZ(CEAZConfig(codebook='exact', **cfg))
    rng = np.random.default_rng(15)
    field = np.cumsum(np.cumsum(rng.standard_normal((64, 64, 16)), 0), 1)
    field = (field / 40 + np.linspace(0, 3, 16)).astype(np.float32)
    origins = [(0, 0, 0), (0, 32, 0), (32, 0, 0), (32, 32, 0)]
    bricks = np.stack([field[i:i + 32, j:j + 32] for i, j, _ in origins])
    ood = bricks.copy()
    ood[2] = (rng.random(ood[2].shape) < 0.01).astype(np.float32)
    FIELDS = ('words', 'block_nbits', 'outlier_idx', 'outlier_delta',
              'bank_index', 'eb', 'n_values')

    def same(a, b):
        return (a.shape == b.shape and len(a.chunks) == len(b.chunks)
                and all(np.array_equal(getattr(ca, f), getattr(cb, f))
                        for ca, cb in zip(a.chunks, b.chunks)
                        for f in FIELDS)
                and np.array_equal(a.literal_idx, b.literal_idx)
                and np.array_equal(a.literal_val, b.literal_val))

    report = {}
    tmp = tempfile.mkdtemp()
    for case, host in (('in', bricks), ('ood', ood)):
        x = jax.device_put(host, NamedSharding(mesh, P('ranks')))
        before = om.snapshot()
        path = os.path.join(tmp, case + '.ceazs')
        collectives.ceaz_gather_stream(
            x, path, comp=comp, origins=origins,
            decomposition={'grid': [2, 2, 1], 'global_shape': [64, 64, 16]})
        d = om.diff(om.snapshot(), before)
        with engine.StreamReader(path) as rd:
            recs = [(rd.records[i], rd.read_object(i))
                    for i in range(len(rd))]
            meta = rd.meta
        arrays = engine.read_stream_arrays(path)
        rep = report[case] = {}
        rep['keys'] = [r['key'] for r, _ in recs]
        rep['shapes'] = [r['shape'] for r, _ in recs]
        rep['origins'] = [r['origin'] for r, _ in recs]
        rep['footer'] = {k: meta.get(k) for k in ('kind', 'decomposition')}
        rep['bank_in_footer'] = 'codebook_bank' in meta
        rep['equal_single'] = [same(c, comp.compress(b))
                               for (_, c), b in zip(recs, host)]
        rep['equal_exact'] = [same(c, exact.compress(b))
                              for (_, c), b in zip(recs, host)]
        rep['bank_coded'] = [all(ch.bank_index >= 0 for ch in c.chunks)
                             for _, c in recs]
        rep['err_over_bound'] = [
            float(np.abs(a.astype(np.float64) - b).max()
                  / (1e-3 * (float(b.max()) - float(b.min()))))
            for a, b in zip(arrays, host)]
        rep['counters'] = {k: v for k, v in d.items()
                           if 'gather' in k or 'fallback' in k
                           or 'collectives' in k}

    # the two programs, as compiled: which collectives each holds
    x = jax.device_put(bricks, NamedSharding(mesh, P('ranks')))
    lay = fused._bank_layout(comp.bank, bricks.shape[1:],
                             comp._chunk_values(32), 1024, 'auto',
                             'lorenzo', True)
    everywhere = NamedSharding(mesh, P())
    args = (x, jax.device_put(np.ones(4, np.float32),
                              NamedSharding(mesh, P('ranks'))),
            jax.device_put(comp.bank.lengths.astype(np.int32), everywhere),
            jax.device_put(comp.bank.code_table().astype(np.uint32),
                           everywhere))
    pass_fn = fused._sharded_pass_fn(lay, mesh, 'ranks')
    out = pass_fn(*args)
    coll = re.compile(r'= (\\S+) (all-gather|all-reduce|reduce-scatter|'
                      r'collective-permute|all-to-all)(?:-start)?\\(')
    report['pass_collectives'] = coll.findall(
        pass_fn.lower(*args).compile().as_text())
    payload = out[:11]
    hlo = collectives._gather_fn(mesh, 'ranks', 11).lower(
        *payload).compile().as_text()
    report['gather_collectives'] = sorted(set(coll.findall(hlo)))
    report['gather_operand_u32'] = sum(
        int(np.prod(a.sharding.shard_shape(a.shape))) for a in payload)
    report['gather_fn_in_pass'] = pass_fn.__name__
    print('REPORT ' + json.dumps(report))
""")


@pytest.fixture(scope="module")
def gather_report():
    import json
    out = run_with_devices(GATHER_SCRIPT, n_devices=4, timeout=300)
    line, = [ln for ln in out.splitlines() if ln.startswith("REPORT ")]
    return json.loads(line[len("REPORT "):])


@pytest.mark.parametrize("case", ["in", "ood"])
def test_gather_records_equal_single_device_compress(gather_report, case):
    """Each rank's record of the gathered stream holds the payload of
    `CEAZ.compress` of its brick alone on one device."""
    assert gather_report[case]["equal_single"] == [True] * 4


@pytest.mark.parametrize("case", ["in", "ood"])
def test_gather_decoded_bricks_within_their_own_bound(gather_report, case):
    errs = gather_report[case]["err_over_bound"]
    assert all(e <= 1.0 for e in errs), errs


def test_gather_drifting_rank_falls_back_alone(gather_report):
    """Brick 2 made spikes trips the drift guard on its rank only: its
    record is the exact path's compress of it, the others stay bank."""
    assert gather_report["in"]["bank_coded"] == [True] * 4
    ood = gather_report["ood"]
    assert ood["bank_coded"] == [True, True, False, True]
    assert ood["equal_exact"][2]
    assert ood["counters"]["ceaz_bank_exact_fallbacks_total"] == 1


@pytest.mark.parametrize("case", ["in", "ood"])
def test_gather_counters_add_up_to_the_pulled_buffer(gather_report, case):
    c = gather_report[case]["counters"]
    pulled = c['ceaz_d2h_bytes_total{side="encode",site="collectives.d2h"}']
    assert c["ceaz_gather_ranks_total"] == 4
    assert 0 < c["ceaz_gather_payload_bytes_total"] < pulled
    assert (c["ceaz_gather_payload_bytes_total"]
            + c["ceaz_gather_pad_bytes_total"]) == pulled
    # the collective's result is every rank's payload buffer
    assert pulled == 4 * 4 * gather_report["gather_operand_u32"]


def test_gather_stream_layout(gather_report):
    rep = gather_report["in"]
    assert rep["keys"] == [f"rank_{r:04d}" for r in range(4)]
    assert rep["shapes"] == [[32, 32, 16]] * 4
    assert rep["origins"] == [[0, 0, 0], [0, 32, 0], [32, 0, 0],
                              [32, 32, 0]]
    assert rep["footer"] == {"kind": "gather", "decomposition": {
        "grid": [2, 2, 1], "global_shape": [64, 64, 16]}}
    assert rep["bank_in_footer"]


def test_gather_only_payload_crosses_devices(gather_report):
    """The bank pass program holds no collective; the gather program
    holds one all-gather, of one uint32 buffer per rank."""
    assert gather_report["gather_fn_in_pass"] == "ceaz_gather_pass"
    assert gather_report["pass_collectives"] == []
    n = gather_report["gather_operand_u32"]
    (shape, op), = gather_report["gather_collectives"]
    assert op == "all-gather" and shape.startswith(f"u32[4,{n}]"), shape
