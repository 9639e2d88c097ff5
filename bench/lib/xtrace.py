"""Reduction of a JAX profiler trace (`.xplane.pb`) to device numbers.

Read with `jax.profiler.ProfileData`, nothing else. On a TPU each chip
is a plane `/device:TPU:<id>`. Its line `XLA Ops` holds one event per
operation that ran on the chip, and `XLA Modules` one event per
execution of a compiled program (a jitted function). The host plane
`/host:CPU` holds the benchmark's `bench.window` and `bench.op`
annotations on the same clock.

  busy      union of the `XLA Ops` intervals inside the window, per chip;
  programs  device time and executions per program name, from
            `XLA Modules`;
  gaps      the intervals inside the window in which no op ran.
"""
from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW = "bench.window"
COLLECTIVE = re.compile(
    r"all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all")
# host spans that mean a thread was waiting, not working: never the name
# of an idle gap
WAITING = re.compile(r"queue_wait|backpressure_stall")


def merge(intervals):
    """Sorted disjoint union of (start, end) intervals."""
    out = []
    for a, b in sorted(i for i in intervals if i[1] > i[0]):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def union_length(intervals) -> float:
    """Total length covered by (start, end) intervals."""
    return sum(b - a for a, b in merge(intervals))


class Reduced:
    """Device numbers of one traced window (seconds, profiler clock)."""

    def __init__(self, window, ops, modules, host_offset):
        self.window = window            # (t0, t1) of `bench.window`
        self.ops = ops                  # chip -> [(name, t0, t1)]
        self.modules = modules          # chip -> [(name, t0, t1)]
        # profiler clock minus host perf_counter, from the window
        self.host_offset = host_offset

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def _clip(self, events):
        w0, w1 = self.window
        return [(n, max(a, w0), min(b, w1)) for n, a, b in events
                if b > w0 and a < w1]

    def busy_by_chip(self):
        return {c: union_length([(a, b) for _, a, b in self._clip(ev)])
                for c, ev in self.ops.items()}

    @property
    def busy_s(self) -> float:
        """Busy seconds averaged over the chips."""
        b = self.busy_by_chip()
        return sum(b.values()) / max(len(b), 1)

    def program_time(self, match=None):
        """{program name: (device seconds, executions)} over all chips,
        divided by the number of chips; `match` filters names."""
        out = {}
        for ev in self.modules.values():
            for n, a, b in self._clip(ev):
                if match is None or match(n):
                    s, k = out.get(n, (0.0, 0))
                    out[n] = (s + b - a, k + 1)
        nc = max(len(self.modules), 1)
        return {n: (s / nc, k / nc) for n, (s, k) in out.items()}

    def executions(self) -> float:
        """Program executions per chip inside the window."""
        return sum(k for _, k in self.program_time().values())

    def op_time(self, match) -> float:
        """Device seconds of ops whose name matches, per chip."""
        t = sum(b - a for ev in self.ops.values()
                for n, a, b in self._clip(ev) if match(n))
        return t / max(len(self.ops), 1)

    def gaps(self, chip=None):
        """Idle intervals of one chip (the first by default)."""
        chip = min(self.ops) if chip is None else chip
        w0, w1 = self.window
        busy = merge([(a, b) for _, a, b in self._clip(self.ops[chip])])
        out, t = [], w0
        for a, b in busy:
            if a > t:
                out.append((t, a))
            t = max(t, b)
        if w1 > t:
            out.append((t, w1))
        return out

    def breakdown(self, spans=None, top: int = 10):
        """The programs that took most device time, and the longest idle
        gaps, each named by the host span (perf_counter clock) that
        covered most of it; spans of a waiting thread are left out."""
        progs = sorted(self.program_time().items(),
                       key=lambda kv: -kv[1][0])[:top]
        gaps = sorted(self.gaps(), key=lambda g: g[0] - g[1])[:top]
        named = []
        for a, b in gaps:
            best, cover = "no host span", 0.0
            for n, s0, s1 in spans or []:
                s0, s1 = s0 + self.host_offset, s1 + self.host_offset
                c = min(b, s1) - max(a, s0)
                if c > cover and not WAITING.search(n):
                    best, cover = n, c
            named.append([best, b - a])
        return {"device_ops": [[n, s] for n, (s, _) in progs],
                "idle_gaps": named}


def _events(line):
    return [(e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
            for e in line.events]


def reduce_profile(pd, chips, window_host_t0: float):
    """Reduce a ProfileData; `chips` are the device ids used, and
    `window_host_t0` the perf_counter second at which the `bench.window`
    annotation opened."""
    ops, modules, window = {}, {}, None
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m and int(m.group(1)) in chips:
            c = int(m.group(1))
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops[c] = _events(line)
                elif line.name == MODULES_LINE:
                    modules[c] = _events(line)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for n, a, b in _events(line):
                    if n == WINDOW:
                        window = (a, b)
    if window is None or not ops:
        raise ValueError(f"trace holds no {WINDOW!r} annotation or no "
                         f"device ops for chips {chips}")
    for c in chips:
        ops.setdefault(c, [])
        modules.setdefault(c, [])
    return Reduced(window, ops, modules, window[0] - window_host_t0)


def reduce_dir(tdir: str, chips, window_host_t0: float) -> Reduced:
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise ValueError(f"{tdir}: {len(paths)} .xplane.pb files")
    return reduce_profile(ProfileData.from_file(paths[0]), chips,
                          window_host_t0)
