"""CPU self-tests of the benchmark's yardstick. Not part of the repo's
tier-1 suite (which collects `tests/` only); run them by hand:

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q
"""
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))

# small sizes of each configuration for runs on the CPU
SMALL = {
    "hacc": {"field_shape": [(1 << 16) + 3001],     # two chunks and a tail
             "compressor": {"mode": "rel", "eb": 1e-4, "codebook": "bank",
                            "use_fused": True, "chunk_bytes": 1 << 17,
                            "block_size": 4096}},
    "nyx": {"field_shape": [32, 32, 64],
            "compressor": {"mode": "rel", "eb": 1e-4, "codebook": "bank",
                           "use_fused": True, "chunk_bytes": 1 << 17,
                           "block_size": 4096}},
}


def small_run(workload, seed=5, hooks=None, seconds=0.5):
    from lib import harness
    cfg = workload.split(".")[0]
    return harness.run(workload, seed, seconds, False, require_tpu=False,
                       cfg_override=SMALL[cfg], hooks=hooks)
