"""The one general traffic generator.

A traffic mix is a JSON file under `bench/traffic/` that names its `op`
and the op's parameters; a configuration is a JSON file under
`bench/configs/`. The op is `bench/ops/<op>.py`, whose `Traffic` class
turns the pair into a closed loop with one caller: set-up (data on the
device, warm-up of each shape the loop uses), then ops back to back
until the window closes, then the check of what the ops produced against
the configuration's plain reference. Its `control()` gives the hooks
that carry the op's output one precision below the configuration's
(`tools/readings.py --control`).

The harness wraps every op in a `bench.op` profiler annotation, so a
trace shows which op the device was serving.
"""
from __future__ import annotations

import sys

import numpy as np

from . import fields, named, reference, work


class Op:
    """What one op did: host-clock start and end, bytes moved."""
    __slots__ = ("t0", "t1", "raw_bytes", "stream_bytes", "chunks",
                 "error")

    def __init__(self, t0, t1, raw_bytes=0, stream_bytes=0, chunks=0,
                 error=None):
        self.t0, self.t1 = t0, t1
        self.raw_bytes, self.stream_bytes = raw_bytes, stream_bytes
        self.chunks, self.error = chunks, error


class Reservoir:
    """A uniform sample of at most `k` of the window's outputs, drawn
    from the seed as they come (the others are dropped at once)."""

    def __init__(self, k: int, seed: int):
        self.k, self.items, self.seen = k, [], 0
        self.rng = np.random.default_rng(seed)

    def offer(self, item):
        if len(self.items) < self.k:
            self.items.append(item)
        else:
            j = int(self.rng.integers(0, self.seen + 1))
            if j < self.k:
                self.items[j] = item
        self.seen += 1


def bf16(x):
    """`x` rounded to bfloat16, the step below float32, as float32."""
    import jax.numpy as jnp
    return jnp.asarray(x).astype(jnp.bfloat16).astype(jnp.float32)


class FieldOps:
    """Base of the ops on one rank's fields: the configuration's
    `fields` fields of `field_shape`, made on the device in set-up from
    its `proxy`, the program's compressor, and the check of a seeded
    sample of the window's outputs against the configuration's
    `reference`. A subclass defines `op(warm)` and `arrays(output)`,
    which turns a sampled output into the arrays to compare."""

    def __init__(self, cfg, mix, seed, workdir, hooks):
        import jax
        from repro.core import CEAZ, CEAZConfig
        self.cfg, self.mix, self.seed = cfg, mix, seed
        self.workdir, self.hooks = workdir, hooks
        self.fields = jax.block_until_ready(fields.make_fields(
            seed, cfg["proxy"], cfg["field_shape"], cfg["fields"]))
        self.comp = CEAZ(CEAZConfig(**cfg["compressor"]))
        self.ref = named.module("references", cfg["reference"])
        self.raw_bytes = int(self.fields[0].nbytes)
        self.chunks = work.chunks_per_field(cfg["field_shape"],
                                            cfg["compressor"]["chunk_bytes"])
        self.checked = Reservoir(mix["checked_ops"], seed)
        self.n = 0

    def warm_up(self):
        self.op(warm=True)
        self.n = 0

    def free(self):
        """Drop what the program holds; the fields stay for the check."""
        self.comp = None

    def check(self) -> dict:
        """The worst reading of each compared number over the sampled
        outputs; inf for every number where an output cannot be read or
        none was sampled."""
        failed = {k: float("inf") for k in self.cfg["limits"]}
        if not self.checked.items:
            return failed
        worst, host = {}, {}
        for k, out in self.checked.items:
            if k not in host:
                host[k] = np.asarray(self.fields[k])
            try:
                arrays = self.arrays(out)
            except (reference.StreamError, KeyError, ValueError) as e:
                print(f"check: {e!r}", file=sys.stderr, flush=True)
                return failed
            for name, v in self.ref.readings(arrays, host[k],
                                             self.cfg).items():
                worst[name] = max(worst.get(name, 0.0), v)
        return worst


def build(cfg, mix, seed, workdir, hooks=None):
    """The op `bench/ops/<mix["op"]>.py`, set up for this cell."""
    return named.module("ops", mix["op"]).Traffic(cfg, mix, seed, workdir,
                                                  hooks or {})
