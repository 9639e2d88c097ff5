"""The plain reference decodes the program's streams by the format
alone, and the canonical code it builds is prefix-free."""
import os

import numpy as np
import pytest

from conftest import SMALL
from lib import fields, named, reference

BANK = named.module("references", "lorenzo_bank")


def test_canonical_codes_are_prefix_free():
    lengths = np.array([3, 3, 2, 3, 4, 4, 0, 2], np.uint8)
    codes = reference.canonical_codes(lengths)
    words = {format(int(codes[s]), f"0{lengths[s]}b")
             for s in np.flatnonzero(lengths)}
    assert len(words) == 7
    for a in words:
        for b in words:
            assert a == b or not b.startswith(a)


@pytest.mark.parametrize("cfg", ["hacc", "nyx"])
def test_reference_decodes_program_stream_within_bound(cfg, tmp_path):
    from repro.core import CEAZ, CEAZConfig
    from repro.io import engine
    small = SMALL[cfg]
    x = fields.make_fields(3, cfg, small["field_shape"], 1)[0]
    comp = CEAZ(CEAZConfig(**small["compressor"]))
    path = os.path.join(tmp_path, "f.ceazs")
    engine.write_stream(path, [x], comp)
    (y,) = reference.decode_stream(path, BANK)
    assert y.shape == tuple(small["field_shape"]) and y.dtype == np.float32
    assert reference.err_over_bound(y, x, 1e-4) <= 1.0
    # the program's own decode gives the same bits
    (z,) = engine.read_stream_arrays(path)
    assert np.array_equal(y, z)


def test_corrupted_stream_is_refused(tmp_path):
    from repro.core import CEAZ, CEAZConfig
    from repro.io import engine
    small = SMALL["hacc"]
    x = fields.make_fields(4, "hacc", small["field_shape"], 1)[0]
    path = os.path.join(tmp_path, "f.ceazs")
    engine.write_stream(path, [x], CEAZ(CEAZConfig(**small["compressor"])))
    data = bytearray(open(path, "rb").read())
    data[100] ^= 0x40
    open(path, "wb").write(bytes(data))
    with pytest.raises(reference.StreamError):
        reference.decode_stream(path, BANK)


def test_err_over_bound_shape_mismatch_is_inf():
    x = np.linspace(0, 1, 64, dtype=np.float32)
    assert reference.err_over_bound(x[:32], x, 1e-4) == float("inf")
    assert reference.err_over_bound(x, x, 1e-4) == 0.0
