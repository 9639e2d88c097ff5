"""The gather cell at a small size on four host devices: `correct` is
true for the program, false for the control and for each fault a gather
stream can have (a rank record dropped, two ranks swapped, a brick
altered past its bound), and a program without the rank-sharded route
stops before set-up; each rank's brick is the proxy made alone at the
brick's shape. One subprocess, since the host devices are fixed
when JAX starts."""
import json
import os
import subprocess
import sys

import pytest

from conftest import BENCH

SMALL = {"field_shape": [32, 32, 16],
         "compressor": {"mode": "rel", "eb": 1e-3, "codebook": "bank",
                        "use_fused": True, "chunk_bytes": 1 << 13,
                        "block_size": 1024}}

SCRIPT = """
import json, sys, time
import numpy as np
sys.path[:0] = [BENCH, BENCH + "/../src"]
from lib import harness, named
from repro.core import CEAZ
from repro.io import collectives

real = collectives.ceaz_gather_stream


def faulty(how):
    def gather(x, path, **kw):
        b = [np.array(a) for a in np.asarray(x)]
        origins = list(kw.pop("origins"))
        if how == "dropped":
            b, origins = b[:-1], origins[:-1]
        elif how == "swapped":
            b[1], b[2] = b[2], b[1]
        else:
            b[1].reshape(-1)[b[1].size // 3] += 1.0
        return real(b, path, origins=origins, **kw)
    return gather


def run(hooks=None):
    r = harness.run("nyx16.gather", 5, 0.5, False, require_tpu=False,
                    cfg_override=SMALL, hooks=hooks)
    return {"correct": r["correct"], "attempted": r["attempted"],
            "failed": r["failed"], "checks": r["checks"],
            "last": list(r)[-1]}


def bricks():
    import jax
    from jax.sharding import Mesh
    from lib import fields
    cfg = dict(harness.load_json(BENCH + "/configs/nyx16.json"), **SMALL)
    cfg["fields"] = 2
    mesh = Mesh(np.array(jax.devices()[:4]), ("ranks",))
    made = named.module("ops", "gather").make_bricks(5, cfg, mesh)
    gen = jax.jit(fields.proxy(cfg["proxy"]).make, static_argnums=1)
    key = fields.seed_key(5)
    err, ranges = 0.0, []
    for k, field in enumerate(made):
        for r, brick in enumerate(np.asarray(field)):
            alone = gen(jax.random.fold_in(jax.random.fold_in(key, k), r),
                        tuple(cfg["field_shape"]))
            err = max(err, float(np.max(np.abs(brick - np.asarray(alone)))))
            ranges.append(float(brick.max() - brick.min()))
    return {"err": err, "ranges": ranges,
            "sharding": [len(f.sharding.device_set) for f in made]}


out = {"bricks": bricks(), "program": run(),
       "control": run(named.module("ops", "gather").control())}
for how in ("dropped", "swapped", "altered"):
    collectives.ceaz_gather_stream = faulty(how)
    out[how] = run()
collectives.ceaz_gather_stream = real
del CEAZ.rank_sharded
t0 = time.perf_counter()
try:
    run()
    out["no_route"] = None
except SystemExit as e:
    out["no_route"] = [str(e), time.perf_counter() - t0]
print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def runs():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=os.environ.get("XLA_FLAGS", "")
               + " --xla_force_host_platform_device_count=4")
    code = f"BENCH = {BENCH!r}\nSMALL = {SMALL!r}\n" + SCRIPT
    res = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr[-3000:]
    line, = [ln for ln in res.stdout.splitlines()
             if ln.startswith("RESULT ")]
    return json.loads(line[len("RESULT "):])


def test_each_rank_makes_its_own_brick(runs):
    # every brick is the proxy made alone at the brick's shape, on its own
    # chip, so its range is the proxy's (about 1) whatever the seed
    b = runs["bricks"]
    assert b["sharding"] == [4, 4]
    assert b["err"] < 1e-5
    assert len(b["ranges"]) == 8
    assert all(abs(r - 1.0) < 0.01 for r in b["ranges"]), b["ranges"]


def test_program_is_correct(runs):
    r = runs["program"]
    assert r["correct"], r["checks"]
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert r["last"] == "checks"


def test_control_is_not_correct(runs):
    r = runs["control"]
    assert not r["correct"]
    # bf16 keeps 8 bits: half an ulp near a brick's top is twice its bound
    c = r["checks"]["max_err_over_bound"]
    assert c["value"] > 2.0 * c["limit"]


@pytest.mark.parametrize("how", ["dropped", "swapped", "altered"])
def test_gather_fault_is_not_correct(runs, how):
    assert not runs[how]["correct"], runs[how]["checks"]


def test_program_without_the_route_stops_at_once(runs):
    msg, seconds = runs["no_route"]
    assert "rank-sharded" in msg and seconds < 5.0
