"""Reference for Lorenzo-predicted, error-bounded (`abs`/`rel`) records
whose chunks are Huffman-coded with the stream's codebook bank.

The bitstreams are decoded from the canonical code that the bank lengths
in the stream's own footer define. The guarantee: every decoded value
within `eb * (max - min)` of the field made from the seed (range-
relative mode), in float64.
"""
import base64

import numpy as np

from lib import reference
from lib.reference import NUM_SYMBOLS, RADIUS, StreamError


def bank_lengths(meta: dict) -> np.ndarray:
    bank = meta["codebook_bank"]
    raw = np.frombuffer(base64.b64decode(bank["lengths"]), np.uint8)
    return raw.reshape(int(bank["n_books"]), NUM_SYMBOLS)


def decode_record(obj, block_size: int, books: np.ndarray,
                  tables: dict) -> np.ndarray:
    """One record, decoded to float32: symbols -> deltas (escape symbol 0
    takes the outlier channel) -> inclusive prefix sums along each
    Lorenzo axis -> q * 2eb in float64, rounded to float32 -> literal
    patches."""
    if obj.predictor != "lorenzo" or obj.mode not in ("abs", "rel"):
        raise StreamError(f"reference decodes Lorenzo abs/rel records, got "
                          f"{obj.predictor}/{obj.mode}")
    deltas = []
    for ch in obj.chunks:
        if ch.bank_index < 0:
            raise StreamError("reference decodes bank-coded chunks only")
        if ch.bank_index not in tables:
            tables[ch.bank_index] = reference.decode_table(
                books[ch.bank_index])
        codes = reference.huffman_decode(ch.words, ch.block_nbits,
                                         ch.n_values, block_size,
                                         tables[ch.bank_index])
        d = codes - RADIUS
        d[np.asarray(ch.outlier_idx, np.int64)] = ch.outlier_delta
        deltas.append(d)
    shape = tuple(obj.shape)
    work = shape if len(shape) <= 3 else (-1,) + shape[-2:]
    q = np.concatenate(deltas).reshape(work)
    for ax in range(obj.ndim):
        q = np.cumsum(q, axis=ax)
    rec = (q.astype(np.float64) * (2.0 * obj.chunks[0].eb)).astype(
        np.float32).reshape(-1)
    rec[np.asarray(obj.literal_idx, np.int64)] = obj.literal_val
    return rec.reshape(shape)


def record_decoder(meta: dict):
    books = bank_lengths(meta)
    block_size = int(meta["block_size"])
    tables = {}
    return lambda obj: decode_record(obj, block_size, books, tables)


def readings(arrays, field, cfg) -> dict:
    """max |decoded - field| / (eb (max - min)) of the one array a field
    decodes to; inf for any other count or shape."""
    if len(arrays) != 1:
        return {"max_err_over_bound": float("inf")}
    return {"max_err_over_bound": reference.err_over_bound(
        arrays[0], field, cfg["compressor"]["eb"])}
