#!/usr/bin/env python3
"""On-chip smoke run of the CEAZ product path, in one process.

    python chip_smoke.py               # one TPU: dump, checkpoint, paging
    python chip_smoke.py --chips 4     # four TPUs: compressed gather only

One chip (no option):
  * field dump: the SDRBench proxies at the paper's sizes (HACC 2^23,
    CESM 1800x3600, NYX 256^3) compressed with the fused facade at rel
    eb 1e-4 under both codebook policies, written with
    `io.engine.write_stream` and read back with `read_stream_arrays`;
    every value within eb * range, the HACC payload byte-identical to
    the staged reference, one fixed-ratio field inside the controller's
    band;
  * checkpoint and serving: ~1 GB of f32 leaves made on the device
    from the seed, `save_checkpoint` -> `restore_checkpoint` ->
    `paged_serving_store`, paged leaves bit-identical to the restore;
  * routes: every dispatch op counted in `ceaz_kernel_calls_total` ran
    the implementation the TPU auto table names.

Four chips (--chips 4): `compressed_all_gather` of a 2^24-value f32
shard per chip against a plain `all_gather`, and the mesh-sharded
`CEAZ.compress_batch` byte-identical to the single-device one.

Exits non-zero, without the result line, unless JAX's first device is a
TPU. The last line of a passing run is one JSON object naming the
device.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import sys
import tempfile
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

REL_EB = 1e-4
FR_TARGET = 10.0
FR_BAND = 0.15          # the paper's fixed-ratio acceptance (Fig 13)
# the dispatch ops the one-chip phases run (no product path calls hufdec)
ROUTED_OPS = ("ceaz_chunk", "ceaz_chunk_dec", "dq_center", "hufenc")


class SmokeFailure(AssertionError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(msg, flush=True)


def _payload_equal(a, b) -> bool:
    from repro.io import engine as E
    return E.serialize_payload(a)[0] == E.serialize_payload(b)[0]


# ---------------------------------------------------------------------------
# One chip
# ---------------------------------------------------------------------------

def field_dump(seed: int, workdir: str, size: str = "medium") -> None:
    """Compress -> .ceazs -> decompress for the paper's three field
    ranks under both codebook policies, plus the staged-reference and
    fixed-ratio checks."""
    from repro.core import CEAZ, CEAZConfig
    from repro.core.dualquant import value_range
    from repro.data import fields as F
    from repro.io import engine as E

    t0 = time.perf_counter()
    fields = [("hacc", F.hacc_proxy(seed, size)),
              ("cesm", F.cesm_proxy(seed + 1, size)),
              ("nyx", F.nyx_proxy(seed + 2, size))]
    log(f"fields made on the host in {time.perf_counter() - t0:.1f}s: "
        + ", ".join(f"{n} {x.shape}" for n, x in fields))
    arrays = [x for _, x in fields]
    for codebook in ("exact", "bank"):
        comp = CEAZ(CEAZConfig(mode="rel", eb=REL_EB, use_fused=True,
                               codebook=codebook))
        path = os.path.join(workdir, f"fields_{codebook}.ceazs")
        t0 = time.perf_counter()
        E.write_stream(path, arrays, comp)
        t_write = time.perf_counter() - t0
        t0 = time.perf_counter()
        back = E.read_stream_arrays(path)
        t_read = time.perf_counter() - t0
        with E.StreamReader(path) as reader:
            payloads = [reader.payload(i) for i in range(len(reader))]
        log(f"[{codebook}] write_stream {t_write:.1f}s, "
            f"read_stream_arrays {t_read:.1f}s")
        for (name, x), y, payload in zip(fields, back, payloads):
            check(y.shape == x.shape and y.dtype == x.dtype,
                  f"{codebook}/{name}: decoded {y.shape} {y.dtype}")
            bound = REL_EB * value_range(x)
            err = float(np.max(np.abs(y.astype(np.float64)
                                      - x.astype(np.float64))))
            log(f"  {name:5s} ratio {x.nbytes / len(payload):.3f} "
                f"max_err {err:.6e} bound {bound:.6e}")
            check(err <= bound, f"{codebook}/{name}: max error {err} "
                                f"> bound {bound}")
        if codebook == "exact":
            # the fused stream's HACC record against the staged host
            # reference (quantization on the device in f32, the rest on
            # the host): byte for byte
            staged = CEAZ(CEAZConfig(mode="rel", eb=REL_EB,
                                     use_fused=False, backend="jax"))
            ref_bytes = E.serialize_payload(staged.compress(arrays[0]))[0]
            same = payloads[0] == ref_bytes
            log(f"  hacc payload vs staged reference: "
                f"{'byte-identical' if same else 'DIFFERENT'} "
                f"({len(ref_bytes)} B)")
            check(same, "hacc: fused payload differs from the staged "
                        "reference")

    name, x = fields[2]
    comp = CEAZ(CEAZConfig(mode="fixed_ratio", target_ratio=FR_TARGET,
                           use_fused=True))
    c = comp.compress(x)
    y = comp.decompress(c)
    err = float(np.max(np.abs(y.astype(np.float64) - x.astype(np.float64))))
    bound = max(ch.eb for ch in c.chunks)
    log(f"fixed_ratio {name}: ratio {c.ratio():.3f} (target {FR_TARGET}, "
        f"band +-{FR_BAND:.0%}), max_err {err:.6e} bound {bound:.6e}")
    check(abs(c.ratio() / FR_TARGET - 1) <= FR_BAND,
          f"fixed_ratio {name}: ratio {c.ratio()} outside the band")
    check(err <= bound, f"fixed_ratio {name}: max error {err} > {bound}")


def _leaf_shapes(total_bytes: int):
    """A transformer-like f32 tree of about `total_bytes`: one embedding
    table and square-ish MLP pairs, 1/8 of the bytes each."""
    unit = max(total_bytes // 8 // 4, 4096)       # values per leaf
    d = 1 << max(6, int(math.log2(unit)) // 2)
    return {"embed": (unit // d, d),
            "layers": [{"wi": (d, unit // d), "wo": (unit // d, d)}
                       for _ in range(3)] + [{"wi": (d, unit // d)}]}


def _by_key(tree) -> dict:
    import jax
    return {jax.tree_util.keystr(p, simple=True, separator="/"):
            np.asarray(v)
            for p, v in jax.tree_util.tree_flatten_with_path(tree)[0]}


def checkpoint_and_serving(seed: int, workdir: str,
                           total_bytes: int = 1 << 30) -> None:
    """save_checkpoint -> restore_checkpoint -> paged_serving_store on a
    ~total_bytes f32 state made on the device from the seed."""
    import jax
    import jax.numpy as jnp
    from repro.checkpoint import ckpt as C
    from repro.core.dualquant import value_range
    from repro.launch import serve as S
    from repro.runtime.sharding import ShardingPlan

    shapes = _leaf_shapes(total_bytes)
    flat_shapes = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda s: isinstance(s, tuple))[0]
    keys = jax.random.split(jax.random.key(seed), len(flat_shapes))

    def make(path, shape, k):
        if path[0].key == "embed":      # smooth rows: the Lorenzo route
            return jnp.cumsum(jax.random.normal(k, shape), axis=1) * 0.01
        return jax.random.normal(k, shape) * 0.02   # noise: value-direct

    leaves = [make(p, s, k) for (p, s), k in zip(flat_shapes, keys)]
    params = jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(
            shapes, is_leaf=lambda s: isinstance(s, tuple)), leaves)
    state = {"params": params, "step": np.int32(7)}
    raw = sum(int(v.nbytes) for v in leaves)
    ckdir = os.path.join(workdir, "ckpt")
    cfg = C.CheckpointConfig()

    t0 = time.perf_counter()
    C.save_checkpoint(ckdir, state, step=7)
    t_save = time.perf_counter() - t0
    stored = os.path.getsize(os.path.join(ckdir, "step_00000007",
                                          C.LEAVES_STREAM))
    t0 = time.perf_counter()
    restored = C.restore_checkpoint(ckdir)
    t_restore = time.perf_counter() - t0
    check(restored is not None, "restore_checkpoint found no checkpoint")
    rstate, meta = restored
    check(meta["step"] == 7, f"restored step {meta['step']}")
    log(f"checkpoint: {raw / 2**20:.0f} MiB raw in {len(leaves)} leaves, "
        f"stream {stored / 2**20:.1f} MiB (ratio {raw / stored:.3f}); "
        f"save {t_save:.1f}s, restore {t_restore:.1f}s")
    orig, back = _by_key(state["params"]), _by_key(rstate["params"])
    check(set(orig) == set(back), "restored tree has other leaves")
    worst = 0.0
    for k, x in orig.items():
        bound = cfg.eb * value_range(x)
        err = float(np.max(np.abs(back[k].astype(np.float64)
                                  - x.astype(np.float64))))
        check(back[k].dtype == x.dtype and back[k].shape == x.shape,
              f"leaf {k}: restored {back[k].shape} {back[k].dtype}")
        check(err <= bound, f"leaf {k}: max error {err} > bound {bound}")
        worst = max(worst, err / bound)
    log(f"  every leaf within its bound (worst max_err/bound {worst:.4f})")

    touch = ["params/embed", "params/layers/1/wo", "params/layers/3/wi"]
    t0 = time.perf_counter()
    opened = S.paged_serving_store(ckdir, ShardingPlan(mesh=None),
                                   dtype=jnp.float32)
    check(opened is not None, "paged_serving_store found no checkpoint")
    store, _ = opened
    with store, store.pin() as pin:
        got = {k: np.asarray(pin.get(k)) for k in touch}
    t_page = time.perf_counter() - t0
    for k in touch:
        ref = back[k[len("params/"):]]
        check(got[k].dtype == ref.dtype
              and got[k].tobytes() == ref.tobytes(),
              f"paged leaf {k} differs from the full restore")
    log(f"paged_serving_store: first touch of {len(touch)} leaves in "
        f"{t_page:.1f}s, bit-identical to the full restore")


def kernel_routes(backend: str = "tpu") -> None:
    """op -> impl from the ceaz_kernel_calls_total counters; every op
    must have run the impl `dispatch` names for `backend`."""
    from repro.kernels import dispatch
    from repro.obs import metrics as om
    ran: dict = {}
    for m in om.DEFAULT.metrics():
        if m.name == om.KERNEL_CALLS and m.value() > 0:
            lab = dict(m.labels)
            ran.setdefault(lab["op"], set()).add(lab["impl"])
    routes = {op: sorted(impls) for op, impls in sorted(ran.items())}
    log("op -> impl: " + json.dumps(routes))
    for op in ROUTED_OPS:
        check(op in routes, f"op {op} never ran")
    for op, impls in routes.items():
        want = dispatch.auto_impl(op, backend)
        check(impls == [want], f"op {op} ran {impls}, the {backend} auto "
                               f"table names {want!r}")


# ---------------------------------------------------------------------------
# Four chips
# ---------------------------------------------------------------------------

def four_chip_gather(seed: int, n: int = 1 << 24) -> None:
    """compressed_all_gather vs plain all_gather, and the mesh-sharded
    compress_batch vs the single-device one."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core import CEAZ, CEAZConfig
    from repro.io.collectives import WireFormat, compressed_all_gather
    from repro.launch import mesh as M
    from repro.runtime.sharding import ShardingPlan

    devs = jax.devices()
    check(len(devs) == 4, f"--chips 4 needs 4 devices, found {len(devs)}")
    mesh = M.make_mesh((4,), ("data",))
    # one smooth random walk per chip, made on the host in bulk
    rng = np.random.default_rng(seed)
    shards = list((rng.standard_normal((4, n), np.float32)
                   .cumsum(axis=1, dtype=np.float32) / 64.0))

    # the batched fused compress, sharded over the mesh, against the
    # same call on one device; it runs first, so a device that was
    # handed a shard shows it in its peak memory
    comp = CEAZ(CEAZConfig(mode="rel", eb=REL_EB, use_fused=True))
    peak0 = [d.memory_stats()["peak_bytes_in_use"] for d in devs]
    t0 = time.perf_counter()
    on_mesh = comp.compress_batch(shards, plan=ShardingPlan(
        mesh=mesh, batch_axes=("data",)))
    t_mesh = time.perf_counter() - t0
    peak1 = [d.memory_stats()["peak_bytes_in_use"] for d in devs]
    t0 = time.perf_counter()
    on_one = comp.compress_batch(shards)
    t_one = time.perf_counter() - t0
    same = [_payload_equal(a, b) for a, b in zip(on_mesh, on_one)]
    log(f"compress_batch 4 x {n} f32: mesh {t_mesh:.1f}s, one device "
        f"{t_one:.1f}s, ratios "
        + " ".join(f"{c.ratio():.3f}" for c in on_mesh)
        + f", byte-identical {same}")
    log(f"  peak bytes per device before/after the mesh pass: "
        f"{peak0} -> {peak1}")
    check(all(same), "mesh-sharded compress_batch differs from one device")
    check(all(b1 - b0 >= 4 * n for b0, b1 in zip(peak0[1:], peak1[1:])),
          "the mesh pass left devices 1..3 without a shard")

    x = jax.device_put(np.stack(shards), NamedSharding(mesh, P("data")))
    placed = sorted(s.device.id for s in x.addressable_shards)
    check(placed == sorted(d.id for d in devs)
          and all(s.data.shape == (1, n) for s in x.addressable_shards),
          f"gather input shards on devices {placed}")

    def plain(v):
        return jax.shard_map(lambda s: jax.lax.all_gather(s, "data"),
                             mesh=mesh, in_specs=P("data", None),
                             out_specs=P(None, "data"))(v)

    eps = float(np.finfo(np.float32).eps)
    for wire in (WireFormat(bits=8, use_lorenzo=False),
                 WireFormat(bits=8, use_lorenzo=True)):
        gfn = jax.jit(lambda v, w=wire: compressed_all_gather(
            v, mesh, "data", w))
        pfn = jax.jit(plain)
        g = jax.block_until_ready(gfn(x))
        p = jax.block_until_ready(pfn(x))
        times = {}
        for name, fn in (("compressed", gfn), ("plain", pfn)):
            t0 = time.perf_counter()
            for _ in range(5):
                jax.block_until_ready(fn(x))
            times[name] = (time.perf_counter() - t0) / 5
        out_devs = sorted(s.device.id for s in g.addressable_shards)
        check(out_devs == sorted(d.id for d in devs),
              f"gather output on devices {out_devs}")
        ratio, exact = _gather_errors(g, p, x, wire, eps)
        log(f"compressed_all_gather bits={wire.bits} "
            f"lorenzo={wire.use_lorenzo}: max err/bound {ratio:.4f}, "
            f"plain gather exact {exact}, "
            f"{times['compressed'] * 1e3:.2f} ms vs plain "
            f"{times['plain'] * 1e3:.2f} ms per call")
        check(exact, "plain all_gather does not return the shards")
        check(ratio <= 1.0, f"compressed gather error {ratio} x its bound")


def _gather_errors(g, p, x, wire, eps):
    """(max |g - p| / bound, whether p holds x exactly). The bound is
    the wire format's: half a quantization step per value for direct
    values; for Lorenzo residuals the half steps of every residual up
    to the value add, as the decode is a running sum. A few float32
    ulps of the value's magnitude per step cover the encode's
    arithmetic."""
    import jax
    import jax.numpy as jnp
    half = float((1 << (wire.bits - 1)) - 1)

    @jax.jit
    def reduce(g, p, x):
        if wire.use_lorenzo:
            prev = jnp.concatenate([jnp.zeros_like(x[:, :1]), x[:, :-1]],
                                   axis=1)
            resid = x - prev
        else:
            resid = x
        scale = jnp.max(jnp.abs(resid), axis=1) / half + 1e-30
        amax = jnp.max(jnp.abs(x), axis=1)
        step = scale / 2 + 8 * eps * amax                       # (R,)
        i = jnp.arange(x.shape[1], dtype=jnp.float32)
        bound = (step[:, None] * (i + 1)[None, :] if wire.use_lorenzo
                 else jnp.broadcast_to(step[:, None], x.shape))
        ratio = jnp.max(jnp.abs(g - p) / bound[:, None, :])
        exact = jnp.all(p == x[:, None, :])
        return ratio, exact

    ratio, exact = reduce(g, p, x)
    return float(ratio), bool(exact)


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip gather phase")
    args = ap.parse_args(argv)

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 2
    from repro.launch.compile_cache import init_compile_cache
    cache = init_compile_cache()
    count = len(jax.devices())
    log(f"device: {dev.device_kind} x {count} ({dev.platform}); "
        f"compile cache {cache}")

    t_all = time.perf_counter()
    if args.chips == 4:
        phases = [("four-chip gather", lambda: four_chip_gather(args.seed))]
    else:
        work = tempfile.mkdtemp(prefix=".smoke_", dir=ROOT)
        phases = [("field dump", lambda: field_dump(args.seed, work)),
                  ("checkpoint and serving",
                   lambda: checkpoint_and_serving(args.seed, work)),
                  ("routes", kernel_routes)]
    try:
        for name, fn in phases:
            t0 = time.perf_counter()
            log(f"== {name}")
            fn()
            log(f"== {name}: passed in {time.perf_counter() - t0:.1f}s")
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    finally:
        if args.chips == 1:
            shutil.rmtree(work, ignore_errors=True)
    log(f"all phases passed in {time.perf_counter() - t_all:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
