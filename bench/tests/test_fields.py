"""The jax.numpy port of the SDRBench proxies keeps their calibration."""
import numpy as np
import pytest

from lib import fields

SMALL = {"hacc": (1 << 18,), "nyx": (64, 64, 64)}
# a rank's share that is no power of two, as the hacc configuration's is
SHARE = {"hacc": ((1 << 18) + 3001,)}


@pytest.mark.parametrize("proxy,shape", [("hacc", SMALL["hacc"]),
                                         ("nyx", SMALL["nyx"]),
                                         ("hacc", SHARE["hacc"])])
@pytest.mark.parametrize("seed", [0, 2**31 + 12345])
def test_sigma_hits_target(proxy, shape, seed):
    x = np.asarray(fields.make_fields(seed, proxy, shape, 1)[0])
    assert x.shape == shape
    rng = float(x.max()) - float(x.min())
    sigma = float(fields.lorenzo_delta_std(x.astype(np.float64))) \
        / (2 * fields.REF_REL_EB * rng)
    # the numpy original lands within 0.3% of its target at this size
    assert sigma == pytest.approx(fields.proxy(proxy).TARGET_SIGMA,
                                  rel=0.03)


def test_same_seed_same_fields_other_seed_other_fields():
    a = fields.make_fields(7, "hacc", (1 << 12,), 2)
    b = fields.make_fields(7, "hacc", (1 << 12,), 3)
    c = fields.make_fields(8, "hacc", (1 << 12,), 2)
    for i in range(2):
        assert np.array_equal(np.asarray(a[i]), np.asarray(b[i]))
        assert not np.array_equal(np.asarray(a[i]), np.asarray(c[i]))
    assert not np.array_equal(np.asarray(a[0]), np.asarray(a[1]))


def test_seed_past_32_bits():
    a = fields.make_fields(2**33 + 1, "hacc", (1 << 10,), 1)[0]
    b = fields.make_fields(1, "hacc", (1 << 10,), 1)[0]
    assert not np.array_equal(np.asarray(a), np.asarray(b))
    with pytest.raises(ValueError):
        fields.seed_key(-1)
