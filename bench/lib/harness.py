"""One run of one cell: set-up, warm-up, measured window, check, result.

Everything that belongs to a configuration, a traffic mix or a metric
is found by name under the benchmark's directory: `configs/<config>.json`
(which names its data proxy, `proxies/<proxy>.py`, and its plain
reference, `references/<reference>.py`), `traffic/<traffic>.json`
(which names its op, `ops/<op>.py`) and each metric's reader (see
`reader`). The end-to-end metrics a cell reports are those of
`BENCHMARK.json`'s `end_to_end` whose `workloads` list the cell (or that
have no such list); with `--trace 1` it reports the `per_layer` metrics
chosen the same way instead.
"""
from __future__ import annotations

import json
import os
import shutil
import sys
import time

from . import named

BENCH_DIR = named.BENCH_DIR
ROOT = os.path.dirname(BENCH_DIR)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
WORK_DIR = os.path.join(ROOT, ".bench_work")
DISK_PROBE_BYTES = 32 << 20


class NoDevice(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def load_spec():
    return load_json(os.path.join(ROOT, "BENCHMARK.json"))


def cell_of(spec, workload: str):
    for w in spec["workloads"]:
        if w["name"] == workload:
            return w
    raise SystemExit(f"unknown workload {workload!r}")


def _for_cell(metrics, cell_name: str, reported=None):
    """Metrics whose `workloads` list this cell; one without the list
    goes wherever the end-to-end metric it moves is reported."""
    out = []
    for m in metrics:
        if "workloads" in m:
            if cell_name in m["workloads"]:
                out.append(m)
        elif reported is None or m.get("moves") in reported:
            out.append(m)
    return out


def reader(name: str):
    """The `read(ctx)` of a metric: `metrics/<name>.py`, or
    `metrics/<name>.json`, which names a shared reader
    `readers/<reader>.py` and its parameters (an `about` key is a note);
    failing both, the same for the longest dotted prefix of the name
    that has a file (`idle_share.dump` -> `metrics/idle_share.py`)."""
    parts = name.split(".")
    for i in range(len(parts), 0, -1):
        stem = os.path.join(BENCH_DIR, "metrics", ".".join(parts[:i]))
        if os.path.exists(stem + ".py"):
            return named.load(stem + ".py").read
        if os.path.exists(stem + ".json"):
            params = load_json(stem + ".json")
            params.pop("about", None)
            read = named.module("readers", params.pop("reader")).read
            return lambda ctx: read(ctx, **params)
    raise FileNotFoundError(f"no reader for metric {name!r}")


def configure_jax():
    """Compile cache at a fixed path in the checkout (or the one
    JAX_COMPILATION_CACHE_DIR names), every program cached."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def devices_for(chips: int, require_tpu: bool = True):
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoDevice(f"needs a TPU, JAX found {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoDevice(f"needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


def disk_write_MBps(workdir: str) -> float:
    """Raw fsynced write speed of the checkout's disk, for reading the
    dump rate against."""
    path = os.path.join(workdir, "disk_probe")
    buf = os.urandom(1 << 20)
    t0 = time.perf_counter()
    with open(path, "wb") as f:
        for _ in range(DISK_PROBE_BYTES >> 20):
            f.write(buf)
        f.flush()
        os.fsync(f.fileno())
    el = time.perf_counter() - t0
    os.unlink(path)
    return DISK_PROBE_BYTES / el / 1e6


class Context:
    """What the metric readers read: the window's ops, the cell, the
    device and, in a traced run, the reduced trace and the obs spans."""

    def __init__(self, cell, cfg, mix, ops, window, device_kind,
                 trace=None, spans=None):
        self.cell, self.cfg, self.mix = cell, cfg, mix
        self.ops = ops
        self.window = window            # (t0, t1), host perf_counter s
        self.device_kind = device_kind
        self.trace = trace              # xtrace.Reduced or None
        self.spans = spans or []        # (name, t0, t1) perf_counter s

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def span_union_s(self, name: str) -> float:
        from .xtrace import union_length
        w0, w1 = self.window
        return union_length([(max(a, w0), min(b, w1))
                             for n, a, b in self.spans
                             if n == name and b > w0 and a < w1])


class CompileCounter:
    """Counts JAX's compile and compile-cache events while `on`: there
    should be none inside the window."""

    def __init__(self):
        import jax
        self.on, self.events = False, []
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event, duration, **kw):
        if self.on and ("compile" in event or "cache" in event):
            self.events.append(event)


def _obs_spans(tracer):
    """obs spans as (name, t0, t1) on the host perf_counter clock."""
    t0 = tracer._t0
    return [(e["name"], t0 + e["ts"] * 1e-6,
             t0 + (e["ts"] + e["dur"]) * 1e-6) for e in tracer.events()]


def run(workload: str, seed: int, seconds: float, trace: bool,
        require_tpu: bool = True, cfg_override=None, hooks=None,
        t_start: float | None = None,
        keep_trace: str | None = None):
    """One run; returns the result object of the contract's last line,
    with the compared numbers last under `checks`, each
    {"value": reading, "limit": limit}. `require_tpu=False`,
    `cfg_override` (small sizes) and `hooks` (an op's `control()`) are
    for the self-tests and the control readings."""
    t_start = time.perf_counter() if t_start is None else t_start
    spec = load_spec()
    cell = cell_of(spec, workload)
    cfg = load_json(os.path.join(BENCH_DIR, "configs",
                                 cell["config"] + ".json"))
    cfg.update(cfg_override or {})
    mix = load_json(os.path.join(BENCH_DIR, "traffic",
                                 cell["traffic"] + ".json"))
    limits = cfg["limits"]

    import jax
    configure_jax()
    devs = devices_for(cell["chips"], require_tpu)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from . import traffic, xtrace

    workdir = os.path.join(WORK_DIR, workload)
    shutil.rmtree(workdir, ignore_errors=True)
    os.makedirs(workdir)
    try:
        log(f"device: {devs[0].device_kind} x {len(devs)}, ready at "
            f"{time.perf_counter() - t_start:.2f} s; disk raw write "
            f"{disk_write_MBps(workdir):.1f} MB/s (fsynced)")
        load = traffic.build(cfg, mix, seed, workdir, hooks)
        t_made = time.perf_counter()
        load.warm_up()
        t_warm = time.perf_counter()
        log(f"set-up: data {t_made - t_start:.2f} s, "
            f"warm-up {t_warm - t_made:.2f} s")

        tracer = tdir = None
        if trace:
            from repro.obs import trace as ot
            tracer = ot.enable()
            tracer.clear()
            tdir = os.path.join(workdir, "trace")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0        # annotations, no calls
            jax.profiler.start_trace(tdir, profiler_options=opts)
        ops = []
        compiles = CompileCounter()
        compiles.on = True
        setup_s = time.perf_counter() - t_start
        w0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.window"):
            while time.perf_counter() - w0 < seconds:
                t0 = time.perf_counter()
                with jax.profiler.TraceAnnotation("bench.op"):
                    try:
                        ops.append(load.op())
                    except Exception as e:      # counted as failed
                        log(f"op failed: {e!r}")
                        ops.append(traffic.Op(t0, time.perf_counter(),
                                              error=repr(e)))
        w1 = max([o.t1 for o in ops] + [time.perf_counter()])
        compiles.on = False
        log(f"compile events inside the window: {len(compiles.events)} "
            f"{sorted(set(compiles.events))}")
        dur = sorted(o.t1 - o.t0 for o in ops)
        log(f"window {w1 - w0:.3f} s, {len(ops)} ops, op seconds min "
            f"{dur[0]:.4f} median {dur[len(dur) // 2]:.4f} max {dur[-1]:.4f}"
            if ops else "window: no op")
        reduced = spans = None
        if trace:
            jax.profiler.stop_trace()
            if keep_trace:
                shutil.copytree(tdir, keep_trace, dirs_exist_ok=True)
            spans = _obs_spans(tracer)
            from repro.obs import trace as ot
            ot.disable()
            t_red = time.perf_counter()
            reduced = xtrace.reduce_dir(tdir, [d.id for d in devs], w0)
            log(f"trace reduced in {time.perf_counter() - t_red:.2f} s")
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devs)

        ctx = Context(cell, cfg, mix, [o for o in ops if o.error is None],
                      (w0, w1), devs[0].device_kind, reduced, spans)
        reported = {m["name"] for m in _for_cell(spec["end_to_end"],
                                                 workload)}
        metrics = {}
        if trace:
            wanted = _for_cell(spec["per_layer"], workload, reported)
        else:
            wanted = [m for m in spec["end_to_end"]
                      if m["name"] in reported and m["name"] != "setup_s"]
        for m in wanted:
            v = reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        if not trace:
            metrics["setup_s"] = {"value": setup_s, "unit": "s"}

        load.free()
        t_check = time.perf_counter()
        readings = load.check()
        log(f"check took {time.perf_counter() - t_check:.2f} s")
    finally:
        shutil.rmtree(workdir, ignore_errors=True)

    failed = sum(o.error is not None for o in ops)
    checks = {k: {"value": v, "limit": limits[k]}
              for k, v in readings.items()}
    correct = (failed == 0 and bool(ops)
               and all(c["value"] <= c["limit"] for c in checks.values()))
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": int(peak)}
    result = {"correct": correct, "attempted": len(ops), "failed": failed,
              "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        result["breakdown"] = reduced.breakdown(spans)
    result["checks"] = checks
    return result
