"""Compressed collectives: the paper's MPI_Gather scenario on a device mesh.

`compressed_all_gather` moves fixed-ratio CEAZ payloads over a mesh axis
instead of raw floats: quantize (stream dual-quant) -> pack b-bit codes ->
all_gather(packed) -> unpack -> reconstruct. Static shapes throughout
(fixed-ratio mode is what makes this jittable — same co-design argument as
the paper's constant-throughput FPGA requirement), and uniform payload
sizes mean the gather has no size-stragglers.

`ceaz_gather_stream` of a rank-sharded `jax.Array` (one brick per device
along a mesh axis) is the error-bounded gather: each device compresses
its own brick in the bank pass, and `gather_to_aggregator` moves the
packed payload buffers, at their static provisioned sizes, to the
aggregator device, which the host then pulls once. Field values never
cross between devices; the padding the static sizes carry is counted
(`ceaz_gather_pad_bytes_total`).

`gather_with_deadline` is the host-level straggler-mitigation wrapper used
by the I/O examples: ranks that miss the deadline are excluded from the
round and their shards backfilled from the previous round (bounded
staleness), which is the standard trick for jittery storage paths.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Callable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from ..obs import metrics as om
from ..obs import trace as ot
from ..optim.grad_compress import pack_jnp, unpack_jnp


@dataclasses.dataclass(frozen=True)
class WireFormat:
    bits: int = 8
    use_lorenzo: bool = True     # stream dual-quant before quantization


def _encode_local(x_flat, bits: int, use_lorenzo: bool):
    """-> (packed u32, scale f32). Static output shapes."""
    half = (1 << (bits - 1)) - 1
    if use_lorenzo:
        # prediction-residual stream: deltas are small on smooth payloads,
        # so the same b bits buy a tighter effective error bound
        shifted = jnp.concatenate([x_flat[:1] * 0, x_flat[:-1]])
        resid = x_flat - shifted
    else:
        resid = x_flat
    scale = jnp.max(jnp.abs(resid)) / half + 1e-30
    q = jnp.clip(jnp.rint(resid / scale), -half, half).astype(jnp.int32)
    return pack_jnp(q + half, bits), scale


def _decode_local(packed, scale, n: int, bits: int, use_lorenzo: bool):
    half = (1 << (bits - 1)) - 1
    q = unpack_jnp(packed, n, bits) - half
    resid = q.astype(jnp.float32) * scale
    if use_lorenzo:
        return jnp.cumsum(resid)
    return resid


def compressed_all_gather(x, mesh: Mesh, axis: str,
                          wire: WireFormat = WireFormat()):
    """x: (n_local, ...) per-rank shard (sharded over `axis`).

    Returns the gathered (n_ranks, n_local, ...) array, having moved only
    packed payloads + scales over the wire. Wire bytes = bits/32 of f32.
    """
    shape = x.shape

    def per_rank(x_loc):
        flat = x_loc.reshape(-1)
        packed, scale = _encode_local(flat, wire.bits, wire.use_lorenzo)
        all_packed = jax.lax.all_gather(packed, axis)
        all_scale = jax.lax.all_gather(scale, axis)
        dec = jax.vmap(lambda p, s: _decode_local(
            p, s, flat.shape[0], wire.bits, wire.use_lorenzo))(
            all_packed, all_scale)
        return dec.reshape((-1,) + x_loc.shape)

    spec = P(axis, *([None] * (len(shape) - 1)))
    return jax.shard_map(per_rank, mesh=mesh, in_specs=spec,
                         out_specs=P(None, axis),
                         axis_names={axis})(x)


@functools.lru_cache(maxsize=None)
def _gather_fn(mesh: Mesh, axis: str, n_arrays: int):
    spec = P(axis)

    def per_rank(*local):
        flat = [(a if a.dtype == jnp.uint32
                 else jax.lax.bitcast_convert_type(a, jnp.uint32))
                .reshape(-1) for a in local]
        # each device's gathered copy, as its own row of the result
        return jax.lax.all_gather(jnp.concatenate(flat), axis)[None]

    def ceaz_gather_payload(*arrays):
        return jax.shard_map(per_rank, mesh=mesh,
                             in_specs=(spec,) * n_arrays, out_specs=spec,
                             axis_names={axis})(*arrays)

    return jax.jit(ceaz_gather_payload)


def gather_to_aggregator(arrays, mesh: Mesh, axis: str, aggregator):
    """Gather rank-sharded 32-bit arrays to the aggregator device.

    Each of `arrays` is split along its leading axis over the mesh axis
    `axis`, rank r's piece on rank r's device. Every rank flattens its
    pieces into one uint32 buffer and the buffers are all-gathered over
    `axis`, which at a host's few ranks costs what a gather to one
    device costs; the host then pulls the aggregator's copy alone.
    Returns, per rank in axis order, its pieces as host arrays of their
    own dtype and per-rank shape."""
    shapes = [(a.sharding.shard_shape(a.shape), a.dtype) for a in arrays]
    if any(np.dtype(d).itemsize != 4 for _, d in shapes):
        raise TypeError("gather_to_aggregator moves 32-bit arrays only")
    with ot.span("collectives.gather", ranks=mesh.shape[axis]):
        out = _gather_fn(mesh, axis, len(arrays))(*arrays)
        agg, = [s.data for s in out.addressable_shards
                if s.device == aggregator]
        with ot.span("collectives.device_wait"):
            agg = jax.block_until_ready(agg)
        with ot.span("collectives.d2h"):
            host = np.asarray(agg)[0]
            om.add(om.D2H_BYTES, int(host.nbytes), side="encode",
                   site="collectives.d2h")
    rows = []
    for buf in host:
        row, off = [], 0
        for shape, dtype in shapes:
            n = int(np.prod(shape))
            row.append(buf[off:off + n].view(dtype).reshape(shape))
            off += n
        rows.append(row)
    return rows


def ceaz_gather(shards, eb_rel: float = 1e-4, plan=None,
                chunk_values: int = 1 << 20, block_size: int = 4096):
    """Host-level compressed gather: the paper's MPI_Gather scenario.

    Every rank's host shard is compressed through the device-resident
    fused pipeline in ONE batched trace, then only the packed payloads
    are 'gathered' (returned with wire-size stats). The facade here
    keeps the exact codebook, so a `plan` carrying a mesh GSPMD-shards
    that batched pass over its batch axes; in bank mode
    `CEAZ.compress_batch` compresses host shards one by one and ignores
    `plan`. Ranks with unequal shard shapes (the usual smaller-last-rank
    case) fall back to per-rank fused passes.
    Returns (compressed_list, stats) where stats reports raw vs wire
    bytes — the paper's Fig 17 quantity.
    """
    from ..core import CEAZ, CEAZConfig
    shards = [np.asarray(s) for s in shards]
    comp = CEAZ(CEAZConfig(mode="rel", eb=eb_rel, use_fused=True,
                           chunk_bytes=4 * chunk_values,
                           block_size=block_size))
    # facade routes: homogeneous f32 -> one batched fused pass; ragged/
    # float64 -> transparent per-shard staged fallback
    comps = comp.compress_batch(shards, plan=plan)
    raw = sum(int(s.nbytes) for s in shards)
    wire = sum(c.nbytes() for c in comps)
    return comps, dict(raw_bytes=raw, wire_bytes=wire,
                       ratio=raw / max(wire, 1), n_ranks=len(comps))


def ceaz_gather_decode(comps, block_size: int = 4096):
    """Aggregator-side inverse of `ceaz_gather`: reconstruct every
    rank's shard from the gathered payloads.

    All ranks' chunks share ONE batched fused Huffman-decode device
    pass (`CEAZ.decompress_batch`); ragged/float64/value-direct payloads
    transparently take the staged host path inside the facade. Returns
    the list of reconstructed arrays in rank order.
    """
    from ..core import CEAZ, CEAZConfig
    comp = CEAZ(CEAZConfig(mode="rel", use_fused=True,
                           block_size=block_size))
    return comp.decompress_batch(comps)


def read_gather_stream(path: str, block_size: Optional[int] = None,
                       group: int = 4):
    """Read an aggregated gather stream back to per-rank arrays.

    The read mirror of `ceaz_gather_stream`: the engine's prefetch
    thread pulls+deserializes rank records while groups decode as one
    batched fused device pass each. By default the decode block grain
    comes from the stream's own footer meta (the writer records it);
    passing `block_size` explicitly takes precedence — for streams
    written before the meta existed, or to force a grain (a mismatch
    with the stream raises rather than decoding garbage). Returns
    (arrays, stats) where stats carries the read/decode overlap
    accounting.
    """
    from ..core import CEAZ, CEAZConfig
    from . import engine as E
    comp = (CEAZ(CEAZConfig(mode="rel", use_fused=True,
                            block_size=block_size))
            if block_size is not None else None)
    with E.AsyncDecodeReadEngine(path, comp, group=group) as eng:
        arrays = [obj for _, obj in eng]
    return arrays, eng.stats.as_dict()


def ceaz_gather_stream(shards, path: str, eb_rel: float = 1e-4,
                       plan=None, chunk_values: int = 1 << 20,
                       block_size: int = 4096, group: int = 2,
                       overlap: bool = True, *, comp=None, origins=None,
                       decomposition=None):
    """Streaming gather: rank shards land in one indexed stream file.

    The aggregator's view of MPI_Gather + write, one record per rank
    (`rank_0000`, `rank_0001`, ... in rank order), under footer meta
    `kind: "gather"`. `comp` is the facade to compress with; by default
    one is built from `eb_rel`, `chunk_values` and `block_size` (the
    exact codebook). `origins[r]`, where given, is rank r's offset in the
    global field and lands in its record meta beside its shape;
    `decomposition` (any JSON object) lands in the footer meta.

    Two routes, chosen from what `shards` is:

    * a rank-sharded `jax.Array` (its leading axis split one brick per
      device along one mesh axis, see `fused.rank_mesh_axis`) under a
      bank-mode `comp`: `comp.compress_batch(shards)` compresses every
      brick on its own device in one program and gathers the packed
      payloads to the aggregator device (`gather_to_aggregator`); the
      records commit through the async engine.
    * host shards (a sequence of arrays, or callables that return one —
      a rank "arriving" is its fetcher being called, so slow ranks
      overlap the commits of earlier ones): each group of `group` shards
      is one `compress_batch` call (with `plan`, which only the exact
      codebook uses) whose payloads commit while the next group
      compresses (two-phase aggregation with the phases overlapped).

    Returns gather stats incl. wire bytes (the Fig 17 quantity).
    """
    from ..core import CEAZ, CEAZConfig
    from . import engine as E
    if comp is None:
        comp = CEAZ(CEAZConfig(mode="rel", eb=eb_rel, use_fused=True,
                               chunk_bytes=4 * chunk_values,
                               block_size=block_size))
    meta = {"kind": "gather", "eb_rel": comp.cfg.eb}
    if decomposition is not None:
        meta["decomposition"] = decomposition

    def record_meta(r, shape, dtype, nbytes):
        m = {"shape": list(shape), "dtype": str(dtype),
             "raw_nbytes": int(nbytes)}
        if origins is not None:
            m["origin"] = [int(o) for o in origins[r]]
        return m

    sharded = comp.rank_sharded(shards)
    compress_fn = ((lambda keys, ranks: comp.compress_batch(shards))
                   if sharded else E.ceaz_compress_fn(comp, plan))
    eng = E.AsyncCompressWriteEngine(
        path, compress_fn, sync=not overlap, meta=meta,
        block_size=comp.cfg.block_size,
        codebook_bank=(comp.bank.to_meta() if comp.bank is not None
                       else None),
        config=comp.cfg)
    with eng:
        if sharded:
            ranks = shards.shape[0]
            brick = shards.shape[1:]
            nbytes = shards.nbytes // ranks
            eng.submit_batch(
                [f"rank_{r:04d}" for r in range(ranks)], list(range(ranks)),
                [record_meta(r, brick, shards.dtype, nbytes)
                 for r in range(ranks)])
        else:
            shards = list(shards)
            for s in range(0, len(shards), max(1, group)):
                grp = [np.asarray(sh() if callable(sh) else sh)
                       for sh in shards[s:s + max(1, group)]]
                eng.submit_batch(
                    [f"rank_{s + j:04d}" for j in range(len(grp))], grp,
                    [record_meta(s + j, a.shape, a.dtype, a.nbytes)
                     for j, a in enumerate(grp)])
    d = eng.stats.as_dict()
    return dict(raw_bytes=d["raw_bytes"], wire_bytes=d["stored_bytes"],
                ratio=d["raw_bytes"] / max(d["stored_bytes"], 1),
                n_ranks=d["n_records"], wall_s=d["wall_s"],
                overlap_efficiency=d["overlap_efficiency"], path=path)


@dataclasses.dataclass
class DeadlineGather:
    """Host-side straggler-tolerant gather (bounded staleness)."""
    deadline_s: float
    last_good: Optional[List[np.ndarray]] = None
    stats: dict = dataclasses.field(
        default_factory=lambda: {"rounds": 0, "dropped": 0})

    def gather(self, fetchers: List[Callable[[], np.ndarray]]):
        """fetchers: one callable per rank returning its (possibly slow)
        shard. Ranks exceeding the per-round deadline are backfilled."""
        out: List[Optional[np.ndarray]] = []
        t0 = time.perf_counter()
        dropped = 0
        for i, fetch in enumerate(fetchers):
            remaining = self.deadline_s - (time.perf_counter() - t0)
            if remaining <= 0 and self.last_good is not None:
                out.append(self.last_good[i])
                dropped += 1
                continue
            out.append(fetch())
        if self.last_good is None:
            self.last_good = list(out)
        else:
            self.last_good = [o if o is not None else lg
                              for o, lg in zip(out, self.last_good)]
        self.stats["rounds"] += 1
        self.stats["dropped"] += dropped
        return out, dropped
