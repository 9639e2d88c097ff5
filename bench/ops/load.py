"""load: set-up dumps the first `streams` of the configuration's fields
once, through the program's `write_stream`; each op reads the next
stream with the program's `read_stream_arrays`, puts the arrays on the
device and blocks until they are there.

Mix parameters: `streams`, and `checked_ops`, the number of the
window's loads, drawn from the seed, held against their fields after
the window.
"""
import os
import time

from lib import traffic


class Traffic(traffic.FieldOps):

    def __init__(self, cfg, mix, seed, workdir, hooks):
        super().__init__(cfg, mix, seed, workdir, hooks)
        from repro.io import engine
        self.streams = []
        for k in range(mix["streams"]):
            path = os.path.join(workdir, f"stream_{k}.ceazs")
            engine.write_stream(path, [self.fields[k]], self.comp)
            self.streams.append(path)

    def op(self, warm: bool = False) -> traffic.Op:
        import jax
        from repro.io import engine
        i = self.n
        self.n += 1
        k = i % len(self.streams)
        t0 = time.perf_counter()
        arrays = engine.read_stream_arrays(self.streams[k])
        arrays = self.hooks.get("loaded", lambda a: a)(arrays)
        out = jax.block_until_ready(jax.device_put(arrays))
        t1 = time.perf_counter()
        if not warm:
            self.checked.offer((k, out))
        return traffic.Op(t0, t1, self.raw_bytes,
                          os.path.getsize(self.streams[k]), self.chunks)

    def arrays(self, out):
        return out


def control() -> dict:
    """The loaded arrays rounded to bfloat16."""
    return {"loaded": lambda arrays: [traffic.bf16(a) for a in arrays]}
