"""Reference for a gather stream: one `.ceazs` file holding one record per
rank of a host's bricks (`rank_0000`, `rank_0001`, ... in rank order),
each a Lorenzo-predicted, error-bounded (`rel`) record whose chunks are
Huffman-coded with the stream's codebook bank.

Each record is decoded by the bank decoder of `lorenzo_bank.py`. The
guarantee: every decoded brick within `eb * (max - min)` of its own
brick of the field made from the seed, since each rank's compressor sees
only its own brick. A record missing, extra, out of rank order, placed
at another origin or of another shape reads inf.
"""
import io
import itertools

from lib import named, reference
from lib.reference import StreamError

record_decoder = named.module("references", "lorenzo_bank").record_decoder


def brick_origins(cfg) -> list:
    """Each rank's offset in the host's share of the field: the bricks of
    `field_shape` laid out over `host_grid`, rank order row-major."""
    return [[i * b for i, b in zip(idx, cfg["field_shape"])]
            for idx in itertools.product(*(range(g)
                                           for g in cfg["host_grid"]))]


def decode_gather(path: str, cfg) -> list:
    """The decoded bricks of a gather stream, in rank order; StreamError
    where its records are not this host's ranks, in order, each at its
    brick's origin."""
    meta, records = reference.read_stream(path)
    if meta.get("kind") != "gather":
        raise StreamError(f"{path}: not a gather stream")
    origins = brick_origins(cfg)
    keys = [rec["key"] for rec, _ in records]
    if keys != [f"rank_{r:04d}" for r in range(len(origins))]:
        raise StreamError(f"{path}: records {keys}")
    if [rec.get("origin") for rec, _ in records] != origins:
        raise StreamError(f"{path}: brick origins")
    decode = record_decoder(meta)
    return [decode(reference._PayloadUnpickler(io.BytesIO(payload)).load())
            for _, payload in records]


def readings(arrays, field, cfg) -> dict:
    """The worst over the ranks of max |decoded - brick| / (eb (max - min)
    of that brick); `field` stacks the bricks. inf for another count of
    arrays, or one of another shape."""
    if len(arrays) != len(field):
        return {"max_err_over_bound": float("inf")}
    eb = cfg["compressor"]["eb"]
    return {"max_err_over_bound": max(
        reference.err_over_bound(a, brick, eb)
        for a, brick in zip(arrays, field))}
