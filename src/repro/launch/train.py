"""Training driver: pjit train step with CEAZ-compressed cross-pod
gradient exchange, preemption-safe loop, compressed checkpointing.

Run (reduced config, CPU):
    PYTHONPATH=src python -m repro.launch.train --arch gemma3-4b \
        --reduced --steps 20 --batch 8 --seq 64
"""
from __future__ import annotations

import argparse
import dataclasses
import signal
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..configs import get_arch
from ..data.synthetic import DataConfig, ShardedDataset, batch_for_step
from ..models import transformer as T
from ..optim import (AdamWConfig, CompressionConfig, adamw_init,
                     adamw_update, compressed_cross_pod_mean, ef_init)
from ..runtime.sharding import ShardingPlan, make_plan, param_shardings
from . import mesh as mesh_lib


@dataclasses.dataclass
class TrainConfig:
    opt: AdamWConfig = dataclasses.field(default_factory=AdamWConfig)
    comp: CompressionConfig = dataclasses.field(
        default_factory=CompressionConfig)
    aux_weight: float = 0.01


def make_plan_for(model_cfg, mesh) -> ShardingPlan:
    plan = make_plan(mesh)
    # pick heads vs head_dim TP per arch (see ShardingPlan.attn_part)
    n_heads = None
    for u in model_cfg.units:
        for b in u.blocks:
            if b.kind == "attn":
                n_heads = b.attn.n_heads
            elif b.kind == "mla":
                n_heads = b.mla.n_heads
    if n_heads is not None and plan.mesh is not None \
       and n_heads % plan.model_size != 0:
        plan = dataclasses.replace(plan, attn_part="head_dim")
    return plan


def init_state(rng, model_cfg, train_cfg: TrainConfig, plan: ShardingPlan):
    params = T.init_params(rng, model_cfg)
    state = {"params": params, "opt": adamw_init(params, train_cfg.opt)}
    if train_cfg.comp.enabled and plan.mesh is not None \
       and "pod" in plan.mesh.axis_names:
        state["residual"] = ef_init(params)
    return state


def state_shardings(state, plan: ShardingPlan):
    ps = param_shardings(state["params"], plan)
    out = {"params": ps,
           "opt": {"mu": param_shardings(state["opt"]["mu"], plan),
                   "nu": param_shardings(state["opt"]["nu"], plan),
                   "step": (NamedSharding(plan.mesh, P())
                            if plan.mesh else None)}}
    if "residual" in state:
        out["residual"] = param_shardings(state["residual"], plan)
    return out


def batch_shardings(batch, plan: ShardingPlan):
    if plan.mesh is None:
        return jax.tree.map(lambda _: None, batch)

    def shard(x):
        parts = (plan.batch,) + (None,) * (np.ndim(x) - 1)
        return NamedSharding(plan.mesh, P(*parts))

    return jax.tree.map(shard, batch)


def has_moe(model_cfg) -> bool:
    return any(b.mlp_kind == "moe" for u in model_cfg.units
               for b in u.blocks)


def make_train_step(model_cfg, train_cfg: TrainConfig, plan: ShardingPlan):
    multi_pod = plan.mesh is not None and "pod" in plan.mesh.axis_names
    use_comp = train_cfg.comp.enabled and multi_pod
    if use_comp and has_moe(model_cfg):
        # jax 0.8.2 Shardy cannot nest the EP shard_map inside the pod-
        # manual compression region (sdy.manual_computation re-binding —
        # see DESIGN.md §limitations). MoE archs exchange uncompressed.
        use_comp = False

    def loss_fn(params, batch, inner_plan):
        return T.lm_loss(params, model_cfg, batch, inner_plan,
                         aux_weight=train_cfg.aux_weight)

    def train_step(state, batch):
        params = state["params"]
        if use_comp:
            # the map is manual over EVERY mesh axis (XLA's SPMD
            # partitioner check-fails on a pod-only manual region): each
            # device computes its pod's gradient on the pod's whole batch,
            # with no sharding constraints inside
            inner_plan = dataclasses.replace(plan, mesh=None)

            def per_pod(params, residual, batch):
                (loss, metr), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(params, batch, inner_plan)
                grads, new_res = compressed_cross_pod_mean(
                    grads, residual, train_cfg.comp)
                loss = jax.lax.pmean(loss, "pod")
                return loss, metr, grads, new_res

            batch_specs = jax.tree.map(
                lambda x: P(*("pod",) + (None,) * (x.ndim - 1)), batch)
            loss, metr, grads, new_res = jax.shard_map(
                per_pod,
                mesh=plan.mesh,
                in_specs=(P(), P(), batch_specs),
                out_specs=(P(), P(), P(), P()),
                axis_names=set(plan.mesh.axis_names),
                check_vma=False,
            )(params, state["residual"], batch)
        else:
            (loss, metr), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch, plan)
            new_res = None
        new_params, new_opt, om = adamw_update(params, grads, state["opt"],
                                               train_cfg.opt)
        new_state = {"params": new_params, "opt": new_opt}
        if new_res is not None:
            new_state["residual"] = new_res
        metrics = {"loss": loss, **metr, **om}
        return new_state, metrics

    return train_step


def jit_train_step(model_cfg, train_cfg, plan, state, batch):
    step_fn = make_train_step(model_cfg, train_cfg, plan)
    if plan.mesh is None:
        return jax.jit(step_fn, donate_argnums=(0,))
    ss = state_shardings(state, plan)
    bs = batch_shardings(batch, plan)
    return jax.jit(step_fn, in_shardings=(ss, bs),
                   out_shardings=(ss, None), donate_argnums=(0,))


# ---------------------------------------------------------------------------
# preemption-safe training loop (checkpoint/restart handled in ckpt module)
# ---------------------------------------------------------------------------

class GracefulStop:
    """SIGTERM/SIGINT => finish the current step, checkpoint, exit.

    This is the node-preemption story: orchestrators deliver SIGTERM with a
    grace window; we always leave a restartable checkpoint behind."""

    def __init__(self):
        self.stop = False
        for sig in (signal.SIGTERM, signal.SIGINT):
            try:
                signal.signal(sig, self._handler)
            except ValueError:
                pass  # not main thread (tests)

    def _handler(self, *_):
        self.stop = True


def train_loop(model_cfg, data_cfg: DataConfig, train_cfg: TrainConfig,
               plan: ShardingPlan, steps: int, ckpt_dir: Optional[str] = None,
               ckpt_every: int = 100, log_every: int = 10,
               start_state: Optional[Dict] = None, start_step: int = 0):
    from ..checkpoint import ckpt as C
    rng = jax.random.key(data_cfg.seed)
    state = start_state or init_state(rng, model_cfg, train_cfg, plan)
    ds = ShardedDataset(data_cfg, start_step=start_step)
    b0 = next(ShardedDataset(data_cfg, start_step=start_step))
    step_fn = jit_train_step(model_cfg, train_cfg, plan, state, b0)
    stopper = GracefulStop()
    history = []
    t0 = time.time()
    for i in range(start_step, steps):
        batch = {k: jnp.asarray(v) for k, v in next(ds).items()}
        state, metrics = step_fn(state, batch)
        if i % log_every == 0 or i == steps - 1:
            loss = float(metrics["loss"])
            history.append((i, loss))
            print(f"step {i:5d} loss {loss:9.4f} "
                  f"gnorm {float(metrics['grad_norm']):8.3f} "
                  f"({(time.time() - t0):6.1f}s)", flush=True)
        should_ckpt = ckpt_dir and (
            (i + 1) % ckpt_every == 0 or i == steps - 1 or stopper.stop)
        if should_ckpt:
            C.save_checkpoint(ckpt_dir, state, step=i + 1,
                              extra={"data": ds.state()})
        if stopper.stop:
            print(f"preemption signal: checkpointed at step {i + 1}, "
                  "exiting cleanly", flush=True)
            break
    return state, history


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--mesh", default="",
                    help="e.g. '2x2' => (data=2, model=2) test mesh")
    args = ap.parse_args(argv)

    spec = get_arch(args.arch)
    model_cfg = spec.reduced() if args.reduced else spec.config()
    mesh = None
    if args.mesh:
        dims = [int(x) for x in args.mesh.split("x")]
        names = ("pod", "data", "model")[-len(dims):]
        mesh = mesh_lib.make_mesh(dims, names)
    plan = make_plan_for(model_cfg, mesh)
    text = args.seq - (model_cfg.frontend_len
                       if model_cfg.frontend == "vision" else 0)
    data_cfg = DataConfig(
        vocab_size=model_cfg.vocab_size, global_batch=args.batch,
        seq_len=text,
        frontend=model_cfg.frontend,
        frontend_len=(model_cfg.encoder.n_frames if model_cfg.encoder
                      else model_cfg.frontend_len),
        frontend_dim=model_cfg.d_model)
    train_cfg = TrainConfig()
    start_state, start_step = None, 0
    if args.resume and args.ckpt_dir:
        from ..checkpoint import ckpt as C
        restored = C.restore_checkpoint(args.ckpt_dir, plan=plan)
        if restored is not None:
            start_state, meta = restored
            start_step = meta["step"]
            print(f"resumed from step {start_step}")
    train_loop(model_cfg, data_cfg, train_cfg, plan, args.steps,
               ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
               start_state=start_state, start_step=start_step)


if __name__ == "__main__":
    main()
