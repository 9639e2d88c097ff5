"""gather: each op writes the next of the configuration's fields, in
turn, for all the host's ranks at once: every rank's brick is compressed
on its own chip and the compressed payloads are gathered to the
aggregator, which writes one fsynced `.ceazs` of one record per rank
(the program's `ceaz_gather_stream` on a rank-sharded array).

Set-up makes each rank's brick of each field from the seed on the
rank's own chip, all chips at once: no field is staged through one chip.
A brick is the proxy made at the brick's shape, so the proxy's anchor (the
Lorenzo delta's scale in quantization units at a range-relative bound)
holds for the data each rank's compressor sees, which bounds by its own
brick's range. Cut from one field made at the host's share, a brick's
range ran from half the share's to all of it with the seed, and the
window's ratio moved with it.

Mix parameters: `checked_ops`, the number of the window's files, drawn
from the seed, that the reference decodes after the window.
"""
import os
import time

from lib import fields, named, traffic, work

AXIS = "ranks"


def make_bricks(seed: int, cfg, mesh):
    """The configuration's fields as (ranks, *field_shape) arrays, one
    brick per device of `mesh`: each device makes its own rank's brick of
    a field from the seed, one field a call. Rank r's brick of field k is
    the proxy at `field_shape` under the key `fold_in(fold_in(seed_key,
    k), r)`, whatever the mesh."""
    import jax
    from jax.sharding import PartitionSpec as P
    brick = tuple(cfg["field_shape"])
    gen = fields.proxy(cfg["proxy"]).make

    def per_rank(key, k):
        rank = jax.lax.axis_index(AXIS)
        return gen(jax.random.fold_in(jax.random.fold_in(key, k), rank),
                   brick)[None]

    make = jax.jit(jax.shard_map(per_rank, mesh=mesh, in_specs=(P(), P()),
                                 out_specs=P(AXIS), axis_names={AXIS}))
    key = fields.seed_key(seed)
    return [jax.block_until_ready(make(key, k)) for k in range(cfg["fields"])]


class Traffic(traffic.FieldOps):

    def __init__(self, cfg, mix, seed, workdir, hooks):
        import jax
        import numpy as np
        from jax.sharding import Mesh
        from repro.core import CEAZ, CEAZConfig
        if not hasattr(CEAZ, "rank_sharded"):
            raise SystemExit("gather: this program has no rank-sharded "
                             "compress route (CEAZ.rank_sharded)")
        self.cfg, self.mix, self.seed = cfg, mix, seed
        self.workdir, self.hooks = workdir, hooks
        self.ref = named.module("references", cfg["reference"])
        self.origins = self.ref.brick_origins(cfg)
        ranks = len(self.origins)
        self.mesh = Mesh(np.array(jax.devices()[:ranks]), (AXIS,))
        self.fields = make_bricks(seed, cfg, self.mesh)
        self.comp = CEAZ(CEAZConfig(**cfg["compressor"]))
        self.raw_bytes = int(self.fields[0].nbytes)
        self.chunks = ranks * work.chunks_per_field(
            cfg["field_shape"], cfg["compressor"]["chunk_bytes"])
        self.decomposition = {
            "grid": cfg["decomposition"],
            "global_shape": cfg["published_field_shape"],
            "host_grid": cfg["host_grid"], "brick": cfg["field_shape"]}
        self.checked = traffic.Reservoir(mix["checked_ops"], seed)
        self.n = 0

    def gather(self, k: int, name: str) -> str:
        from repro.io import collectives
        path = os.path.join(self.workdir, name + ".ceazs")
        collectives.ceaz_gather_stream(
            self.fields[k], path, comp=self.comp, origins=self.origins,
            decomposition=self.decomposition)
        return path

    def op(self, warm: bool = False) -> traffic.Op:
        i = self.n
        self.n += 1
        k = i % len(self.fields)
        t0 = time.perf_counter()
        path = self.gather(k, "warm" if warm else f"gather_{i:05d}")
        t1 = time.perf_counter()
        size = os.path.getsize(path)
        if warm:
            os.unlink(path)
        else:
            self.checked.offer((k, path))
        return traffic.Op(t0, t1, self.raw_bytes, size, self.chunks)

    def arrays(self, path):
        decoded = self.ref.decode_gather(path, self.cfg)
        return self.hooks.get("decoded", lambda a: a)(decoded)


def control() -> dict:
    """The reference's decode of each checked file's bricks rounded to
    bfloat16."""
    return {"decoded": lambda arrays: [traffic.bf16(a) for a in arrays]}
