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
