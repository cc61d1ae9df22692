"""The traced window: profiler on, host spans, and the reduction from the
profiler's trace to device busy time, idle gaps and kernel time.

The reduction works on plain tuples, ``(name, start_ns, end_ns)``, so the
same code runs on a trace read from the profiler's ``.xplane.pb`` and on a
small recorded excerpt in the tests.
"""
from __future__ import annotations

import contextlib
import glob
import os
import re
import shutil
import time

# Host spans the benchmark records around its calls into each layer. They
# go into the profiler's trace (jax.profiler.TraceAnnotation), on the same
# clock as the device's operations, and name what the host was doing.
SPAN_PREFIX = "bench."


def annotate(name: str):
    """A host span named ``bench.<name>``; a no-op when JAX is absent."""
    import jax

    return jax.profiler.TraceAnnotation(SPAN_PREFIX + name)


class Window:
    """The measured window. With ``trace`` on it runs under the profiler,
    inside one ``bench.window`` span; ``t0``/``t1`` are host-clock seconds.
    The compile counter's reading at entry and exit gives the compilations
    inside the window."""

    def __init__(self, trace_dir: str | None, compiles):
        self.trace_dir = trace_dir
        self.compiles = compiles
        self.t0 = self.t1 = None
        self.compiles_inside = None
        self._stack = contextlib.ExitStack()

    def __enter__(self):
        if self.trace_dir is not None:
            import jax

            shutil.rmtree(self.trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0     # no per-call Python events
            opts.host_tracer_level = 2       # keep TraceAnnotation spans
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
            self._stack.enter_context(annotate("window"))
        self._c0 = self.compiles()
        self.t0 = time.perf_counter()
        return self

    def close(self) -> None:
        """End the window (the driver calls this at the window's last
        boundary); the profiler stops when the ``with`` block ends."""
        if self.t1 is None:
            self.t1 = time.perf_counter()
            self.compiles_inside = self.compiles() - self._c0
            self._stack.close()

    def __exit__(self, *exc):
        self.close()
        if self.trace_dir is not None:
            import jax

            jax.profiler.stop_trace()
        return False

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


# ----------------------------------------------------------- reading traces
_DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)$")
# The line of a device plane that holds one event per executed operation.
OPS_LINE = "XLA Ops"


def read_trace(trace_dir: str):
    """(device ops, host spans) from the newest trace under ``trace_dir``.

    Device ops: ``{device index: [(name, start_ns, end_ns), ...]}`` from
    each device plane's ``XLA Ops`` line. Host spans: every host event
    whose name starts with ``bench.``. Both on the profiler's clock."""
    import jax

    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        return {}, []
    data = jax.profiler.ProfileData.from_file(paths[-1])
    ops, spans = {}, []
    for plane in data.planes:
        m = _DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == OPS_LINE:
                ops.setdefault(int(m.group(2)), []).extend(
                    (e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events)
            elif not m:
                spans.extend(
                    (e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events if e.name.startswith(SPAN_PREFIX))
    return ops, spans


def window_of(spans) -> tuple[float, float] | None:
    """(start_ns, end_ns) of the ``bench.window`` span."""
    for name, a, b in spans:
        if name == SPAN_PREFIX + "window":
            return a, b
    return None


def merge_intervals(intervals, lo: float, hi: float):
    """Union of ``(start, end)`` intervals clipped to [lo, hi], sorted."""
    out = []
    for a, b in sorted((max(a, lo), min(b, hi)) for a, b in intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def busy_ns(events, lo: float, hi: float) -> float:
    """Time in [lo, hi] in which at least one operation ran."""
    return sum(b - a for a, b in merge_intervals(
        [(a, b) for _, a, b in events], lo, hi))


def idle_gaps(events, lo: float, hi: float):
    """The intervals of [lo, hi] in which no operation ran."""
    gaps, t = [], lo
    for a, b in merge_intervals([(a, b) for _, a, b in events], lo, hi):
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def label_gap(gap, spans) -> str:
    """What the host was doing in an idle gap: the innermost benchmark span
    (the shortest) that covers the gap's midpoint, else ``host``."""
    mid = 0.5 * (gap[0] + gap[1])
    cover = [(b - a, name) for name, a, b in spans
             if a <= mid <= b and name != SPAN_PREFIX + "window"]
    return min(cover)[1] if cover else "host"


def op_name(name: str) -> str:
    """An operation's own name from the trace's HLO text, without its
    instance number: ``%topk_score.1 = (f32[64,128]...) custom-call(...)``
    → ``topk_score``; ``fusion.12`` → ``fusion``."""
    head = name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"[.:]\d+$", "", head)


def op_label(name: str) -> str:
    """An operation as the breakdown lists it: its instance name and result
    shape (``fusion.89 = f32[20000000]``), one entry per program op."""
    return re.split(r"[{]", name, 1)[0].lstrip("%").strip()


def kernel_ns(events, kernel: str, lo: float, hi: float) -> float:
    """Device time of the operations named ``kernel`` (see :func:`op_name`)."""
    return sum(min(b, hi) - max(a, lo) for name, a, b in events
               if op_name(name) == kernel and min(b, hi) > max(a, lo))


def breakdown(events, spans, lo: float, hi: float, n: int = 10) -> dict:
    """The device operations that took most time, and the longest idle
    gaps by what the host was doing, as [name, seconds] lists."""
    tot = {}
    for name, a, b in events:
        if min(b, hi) > max(a, lo):
            g = op_label(name)
            tot[g] = tot.get(g, 0.0) + (min(b, hi) - max(a, lo))
    top = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    gaps = sorted(idle_gaps(events, lo, hi), key=lambda g: g[0] - g[1])[:n]
    return {
        "device_ops": [[k, v * 1e-9] for k, v in top],
        "idle_gaps": [[label_gap(g, spans), (g[1] - g[0]) * 1e-9]
                      for g in gaps],
    }
