"""The program's always-on counters (`repro.obs.metrics`), summed over
the label sets that hold the given labels. They are cumulative over the
process: set-up and warm-up count too, which a ratio of two counters
may ignore where every op of a cell does the same work. A program
without a counter reads 0 for it."""

SIDE = {"dump": "encode", "load": "decode"}     # a mix's op -> its side


def side(ctx):
    """The side of the pipeline a cell's ops run: `encode` or `decode`."""
    return SIDE.get(ctx.mix.get("op"))


def total(name: str, **labels) -> float:
    try:
        from repro.obs import metrics as om
    except ImportError:
        return 0
    return sum(m.value() for m in om.DEFAULT.metrics()
               if m.name == name
               and all(dict(m.labels).get(k) == v
                       for k, v in labels.items()))
