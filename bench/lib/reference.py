"""The plain reference that decides `correct`: what every configuration's
reference shares.

Independent of the program: nothing here imports it or takes a table it
made. A `.ceazs` stream is read by the byte layout of the format's
specification (magic, records, JSON footer, trailer), and its `ceaz`
payloads are unpickled into plain records. A configuration names its
reference module, `bench/references/<name>.py`, which decodes those
records (`record_decoder(meta)`) and holds the decoded arrays to the
configuration's guarantee (`readings(arrays, field, cfg)`); the
canonical-Huffman decoding and the error bound here are shared by them.
"""
from __future__ import annotations

import io
import json
import pickle
import struct
import zlib

import numpy as np

STREAM_MAGIC = b"CEAZS\x01\x00\x00"
END_MAGIC = b"CEAZSEND"
RECORD_HEADER = struct.Struct("<4sIQ")
TRAILER = struct.Struct("<QQI8s")
NUM_SYMBOLS = 1024
RADIUS = 512
MAX_LEN = 16


class StreamError(ValueError):
    """The stream breaks the format or cannot be decoded by it."""


class _Record:
    """Plain stand-in for a pickled payload object: its attributes only."""

    def __setstate__(self, state):
        if isinstance(state, tuple):        # (dict, slots) form
            state = {**(state[0] or {}), **(state[1] or {})}
        self.__dict__.update(state)


class _PayloadUnpickler(pickle.Unpickler):
    """Unpickles `ceaz` payloads without the program: its two record
    classes become `_Record`; numpy's array reconstruction is the only
    other global allowed."""

    _NUMPY = {("numpy._core.multiarray", "_reconstruct"),
              ("numpy.core.multiarray", "_reconstruct"),
              ("numpy", "ndarray"), ("numpy", "dtype")}

    def find_class(self, module, name):
        if name in ("CEAZCompressed", "CompressedChunk"):
            return _Record
        if (module, name) in self._NUMPY:
            return super().find_class(module, name)
        raise StreamError(f"payload references {module}.{name}")


def read_stream(path: str):
    """(meta, [(index row, payload bytes)]) of a `.ceazs` file, with the
    trailer, footer checksum, record headers and payload checksums
    verified."""
    with open(path, "rb") as f:
        data = f.read()
    if data[:8] != STREAM_MAGIC or len(data) < 8 + TRAILER.size:
        raise StreamError(f"{path}: bad magic or truncated")
    off, flen, fcrc, magic = TRAILER.unpack(data[-TRAILER.size:])
    if magic != END_MAGIC or off + flen + TRAILER.size != len(data):
        raise StreamError(f"{path}: bad trailer")
    footer = data[off:off + flen]
    if zlib.crc32(footer) & 0xFFFFFFFF != fcrc:
        raise StreamError(f"{path}: footer checksum")
    doc = json.loads(footer)
    records = []
    for i, rec in enumerate(doc["records"]):
        o = rec["offset"]
        tag, seq, n = RECORD_HEADER.unpack(data[o:o + RECORD_HEADER.size])
        payload = data[o + RECORD_HEADER.size:o + RECORD_HEADER.size + n]
        if (tag != b"SHRD" or seq != i or n != rec["nbytes"]
                or zlib.crc32(payload) & 0xFFFFFFFF != rec["crc32"]):
            raise StreamError(f"{path}: record {i} header or checksum")
        records.append((rec, payload))
    return doc["meta"], records


def canonical_codes(lengths: np.ndarray) -> np.ndarray:
    """Canonical Huffman codes: symbols in order of (length, symbol),
    each code the previous plus one, shifted left by the length step."""
    codes = np.zeros(len(lengths), np.int64)
    code, prev = 0, None
    for s in sorted(np.flatnonzero(lengths), key=lambda s: (lengths[s], s)):
        ln = int(lengths[s])
        if prev is not None:
            code = (code + 1) << (ln - prev)
        codes[s] = code
        prev = ln
    return codes


def decode_table(lengths: np.ndarray):
    """(symbol, length) for every MAX_LEN-bit window."""
    codes = canonical_codes(lengths)
    sym = np.zeros(1 << MAX_LEN, np.int64)
    ln = np.zeros(1 << MAX_LEN, np.int64)
    for s in np.flatnonzero(lengths):
        l = int(lengths[s])
        lo = int(codes[s]) << (MAX_LEN - l)
        sym[lo:lo + (1 << (MAX_LEN - l))] = s
        ln[lo:lo + (1 << (MAX_LEN - l))] = l
    return sym, ln


def _bits(words: np.ndarray) -> np.ndarray:
    """The MSB-first bit string of uint64 words, as 0/1 bytes."""
    be = words.astype(">u8").view(np.uint8)
    return np.unpackbits(be)


def huffman_decode(words, block_nbits, n_values, block_size, table):
    """Symbols of one chunk: blocks start where the previous one's bits
    end; all blocks are walked together, one symbol per step."""
    sym_t, len_t = table
    bits = np.concatenate([_bits(np.asarray(words, np.uint64)),
                           np.zeros(MAX_LEN, np.uint8)])
    weights = (1 << np.arange(MAX_LEN - 1, -1, -1)).astype(np.int64)
    nb = len(block_nbits)
    cursor = np.concatenate([[0], np.cumsum(block_nbits)[:-1]]).astype(
        np.int64)
    end = cursor + np.asarray(block_nbits, np.int64)
    counts = np.full(nb, block_size, np.int64)
    counts[-1] = n_values - (nb - 1) * block_size
    out = np.zeros((nb, block_size), np.int64)
    win = np.arange(MAX_LEN)
    for i in range(block_size):
        live = counts > i
        if not live.any():
            break
        idx = bits[cursor[:, None] + win[None, :]] @ weights
        out[:, i] = np.where(live, sym_t[idx], 0)
        cursor = cursor + np.where(live, len_t[idx], 0)
    if not np.array_equal(cursor, end):
        raise StreamError("a block's bits do not end where its count says")
    return out.reshape(-1)[:n_values]


def decode_stream(path: str, ref):
    """Every record of a `.ceazs` stream, decoded by the reference module
    `ref` (`bench/references/<name>.py`, named by the configuration)."""
    meta, records = read_stream(path)
    decode = ref.record_decoder(meta)
    return [decode(_PayloadUnpickler(io.BytesIO(payload)).load())
            for _, payload in records]


def err_over_bound(decoded, field, eb_rel: float) -> float:
    """max |decoded - field| / (eb_rel * (max - min)), in float64; inf
    for a decoded array of another shape."""
    x = np.asarray(field).astype(np.float64)
    y = np.asarray(decoded)
    if y.shape != x.shape:
        return float("inf")
    rng = float(x.max()) - float(x.min())
    bound = eb_rel * (rng if rng > 0 else 1.0)
    return float(np.max(np.abs(y.astype(np.float64) - x))) / bound
