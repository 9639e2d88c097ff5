"""dump: each op writes the next of the configuration's fields, in
turn, from its device array into its own fsynced `.ceazs` file through
the program's `write_stream` (SDRBench keeps one file per field).

Mix parameters: `checked_ops`, the number of the window's files, drawn
from the seed, that the reference decodes after the window.
"""
import os
import time

from lib import reference, traffic


class Traffic(traffic.FieldOps):

    def dump(self, k: int, name: str) -> str:
        from repro.io import engine
        path = os.path.join(self.workdir, name + ".ceazs")
        engine.write_stream(path, [self.fields[k]], self.comp)
        return path

    def op(self, warm: bool = False) -> traffic.Op:
        i = self.n
        self.n += 1
        k = i % len(self.fields)
        t0 = time.perf_counter()
        path = self.dump(k, "warm" if warm else f"dump_{i:05d}")
        t1 = time.perf_counter()
        size = os.path.getsize(path)
        if warm:
            os.unlink(path)
        else:
            self.checked.offer((k, path))
        return traffic.Op(t0, t1, self.raw_bytes, size, self.chunks)

    def arrays(self, path):
        decoded = reference.decode_stream(path, self.ref)
        return self.hooks.get("decoded", lambda a: a)(decoded)


def control() -> dict:
    """The reference's decode of each checked file rounded to bfloat16.
    (Rounding the field before the dump instead makes the program leave
    the bank codebooks for the exact path.)"""
    return {"decoded": lambda arrays: [traffic.bf16(a) for a in arrays]}
