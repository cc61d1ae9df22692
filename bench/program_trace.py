"""The program's own spans and scopes, read from the traced window.

Two things of the program land in the profiler's trace on the device's
clock, and this module reads them beside the ``bench.`` spans that
``bench.tracing`` reads:

- host spans named ``repro.<name>``: every span of ``repro.obs.Tracer``
  (``request``, ``queue``, ``flush`` and its phases ``assemble``,
  ``transfer``, ``dispatch``, ``wait``, ``route``) is also a profiler
  annotation;
- ``icd.*`` named scopes in the training step (``core/models/mf.py``,
  ``core/sweeps.py``), which the compiler keeps in each operation's
  ``op_name`` metadata. The trace does not carry that metadata (on a TPU
  v5e with JAX 0.9 an op event's stats hold only its device times), so an
  operation's scope is looked up by its instruction name in the compiled
  program that ran it (the ``XLA Modules`` event around it), which this
  process holds. A fusion counts under the scope of its root instruction.

Like ``bench.tracing``, the reductions work on plain tuples, so the same
code runs on a trace and on a small recorded excerpt in the tests. On a
program that has no such spans or scopes every reading is ``None``.
"""
from __future__ import annotations

import bisect
import functools
import gc
import glob
import heapq
import os
import re
import traceback

from bench import tracing

SPAN_PREFIX = "repro."
SCOPE_PREFIX = "icd."
MODULES_LINE = "XLA Modules"
FLUSH = SPAN_PREFIX + "flush"
# the scopes of the step's traffic through the pair layout other than its
# reductions (train.gather_ms), and its reductions (train.segsum_ms)
LAYOUT_SCOPES = ("icd.gather", "icd.patch", "icd.permute")
SEGSUM_SCOPES = ("icd.segsum",)
UNSCOPED = "unscoped"
_DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)$")

_SCOPE = re.compile(r"(?:^|/)(icd\.[A-Za-z_]+)")
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def scope_of(op_name: str | None) -> str | None:
    """The innermost ``icd.*`` scope in an ``op_name`` path, or None."""
    found = _SCOPE.findall(op_name or "")
    return found[-1] if found else None


def instr_name(event_name: str) -> str:
    """An operation's instruction name from its trace event's name:
    ``%fusion.89 = f32[...] fusion(...)`` → ``fusion.89``."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def hlo_scopes(text: str) -> dict:
    """``{instruction name: icd scope or None}`` of one HLO module's text.
    An instruction without a scope of its own takes that of the
    computation it calls (a fusion's): its root's, or where the compiler
    left the root without metadata, that of the last scoped instruction
    before it."""
    own, calls, comps, comp = {}, {}, {}, None
    for line in text.splitlines():
        if " = " not in line:
            if line.rstrip().endswith("{"):     # a computation's header
                comp = line.split("(", 1)[0].split()[-1].lstrip("%")
            continue
        head = line.split(" = ", 1)[0].split()
        name = head[-1].lstrip("%")
        m = _OP_NAME.search(line)
        own[name] = scope_of(m.group(1)) if m else None
        m = _CALLS.search(line)
        if m:
            calls[name] = m.group(1)
        if comp is not None and (own[name] or head[0] == "ROOT"):
            comps[comp] = own[name] or comps.get(comp)
    return {n: s if s is not None else comps.get(calls.get(n))
            for n, s in own.items()}


def held_programs(wanted) -> dict:
    """``{module name: {instruction: scope}}`` of the compiled programs
    this process holds whose module is named in ``wanted``.

    An executable loaded from the persistent compilation cache carries the
    metadata of the build that wrote the entry (the cache key leaves
    metadata out), so an older build's entry has no scopes. Where the
    lowered program has scopes that its executable lacks, it is compiled
    again from a copy of the lowered module, under a key that includes
    the metadata: the same program, with this build's metadata."""
    out = {}
    for comp in gc.get_objects():
        if type(comp).__name__ != "MeshComputation" \
                or comp._executable is None:
            continue
        mod = comp._executable.xla_extension_executable().hlo_modules()[0]
        if mod.name not in wanted:
            continue
        scopes = hlo_scopes(mod.to_string())
        if not any(scopes.values()):
            asm = comp._hlo.operation.get_asm(enable_debug_info=True)
            if SCOPE_PREFIX in asm:
                try:
                    scopes = hlo_scopes(_recompiled(comp, asm).to_string())
                except Exception:   # a reader must not fail the run
                    log(f"no fresh compile of {mod.name}:\n"
                        f"{traceback.format_exc()}")
        prog = out.setdefault(mod.name, {})
        for name, scope in scopes.items():    # a scope outranks none
            if scope is not None or name not in prog:
                prog[name] = scope
    return out


def _recompiled(comp, asm: str):
    """The HLO module of ``comp`` compiled again from ``asm`` (its lowered
    text with locations): a new module object misses JAX's in-process
    cache, and a key with metadata misses the older build's entry."""
    import jax
    from jax._src.interpreters import pxla
    from jax._src.lib.mlir import ir

    with comp._hlo.context:
        module = ir.Module.parse(asm)
    flag = "jax_compilation_cache_include_metadata_in_key"
    before = getattr(jax.config, flag)
    jax.config.update(flag, True)
    try:
        exe = pxla.UnloadedMeshExecutable.from_hlo(
            comp._name, module, **comp.compile_args,
            compiler_options_kvs=comp._compiler_options_kvs,
            device_list=comp._device_list)
    finally:
        jax.config.update(flag, before)
    return exe.xla_extension_executable().hlo_modules()[0]


def module_name(event_name: str) -> str:
    """``jit_epoch(6536856975076015183)`` → ``jit_epoch``."""
    return event_name.split("(", 1)[0]


def scoped_ops(ops, modules, programs):
    """``(name, start_ns, end_ns, scope)`` per device operation, its scope
    looked up in ``programs`` under the module whose interval holds the
    op's start (``modules``: ``(name, start_ns, end_ns)``)."""
    mods = sorted(modules, key=lambda m: m[1])
    starts = [m[1] for m in mods]
    out = []
    for name, a, b in ops:
        scope, i = None, bisect.bisect_right(starts, a) - 1
        if i >= 0 and a <= mods[i][2]:
            prog = programs.get(module_name(mods[i][0]), {})
            scope = prog.get(instr_name(name))
        out.append((name, a, b, scope))
    return out


def scope_ns(ops, lo: float, hi: float) -> dict:
    """Device time in [lo, hi] by scope, ops with none under ``unscoped``."""
    tot = {}
    for _, a, b, scope in ops:
        d = min(b, hi) - max(a, lo)
        if d > 0:
            key = scope or UNSCOPED
            tot[key] = tot.get(key, 0.0) + d
    return tot


# ------------------------------------------------------------ serving side
def flush_idle_ns(events, spans, lo: float, hi: float) -> list:
    """Per ``repro.flush`` span in [lo, hi]: the time inside it (clipped
    to the window) in which no operation ran on the device."""
    gaps = tracing.idle_gaps(events, lo, hi)
    ends = [b for _, b in gaps]
    out = []
    for name, a, b in spans:
        a, b = max(a, lo), min(b, hi)
        if name != FLUSH or b <= a:
            continue
        idle, i = 0.0, bisect.bisect_right(ends, a)
        while i < len(gaps) and gaps[i][0] < b:
            idle += min(b, gaps[i][1]) - max(a, gaps[i][0])
            i += 1
        out.append(idle)
    return out


def innermost(points, spans) -> list:
    """For each point (ns), the innermost span (the shortest) of ``spans``
    that covers it, else ``host``: ``tracing.label_gap``'s rule, in one
    sweep over spans sorted by start instead of a scan per point."""
    window = tracing.SPAN_PREFIX + "window"
    todo = sorted((a, b, name) for name, a, b in spans if name != window)
    order = sorted(range(len(points)), key=lambda i: points[i])
    out, heap, j = [None] * len(points), [], 0
    for i in order:
        t = points[i]
        while j < len(todo) and todo[j][0] <= t:
            a, b, name = todo[j]
            heapq.heappush(heap, (b - a, name, b))
            j += 1
        while heap and heap[0][2] < t:      # ended before t: never again
            heapq.heappop(heap)
        out[i] = heap[0][1] if heap else "host"
    return out


def labelled_gaps(events, spans, lo: float, hi: float,
                  min_ns: float = 0.0) -> list:
    """Idle gaps of at least ``min_ns`` as ``(label, start_ns, ns)``,
    longest first, each labelled by the innermost span of either prefix
    (``bench.`` or ``repro.``) that covers its midpoint."""
    gaps = [g for g in tracing.idle_gaps(events, lo, hi)
            if g[1] - g[0] >= min_ns]
    labels = innermost([0.5 * (a + b) for a, b in gaps], spans)
    return sorted(((lab, a, b - a) for lab, (a, b) in zip(labels, gaps)),
                  key=lambda x: -x[2])


def flush_phases(program_spans) -> dict:
    """Mean host-clock milliseconds of each child of the batcher's
    ``flush`` span by name, and the flush's own: the whole, and its self
    time (the whole less the union of its children)."""
    by_parent = {}
    for sp in program_spans:
        by_parent.setdefault(sp.parent_id, []).append(sp)
    tot, n, total, self_s = {}, 0, 0.0, 0.0
    for fl in program_spans:
        if fl.name != "flush" or fl.t1 is None:
            continue
        kids = [k for k in by_parent.get(fl.span_id, ()) if k.t1 is not None]
        for k in kids:
            tot[k.name] = tot.get(k.name, 0.0) + k.duration
        cov = sum(b - a for a, b in tracing.merge_intervals(
            [(k.t0, k.t1) for k in kids], fl.t0, fl.t1))
        n += 1
        total += fl.duration
        self_s += fl.duration - cov
    if not n:
        return {}
    out = {k: 1e3 * v / n for k, v in tot.items()}
    out.update(flush=1e3 * total / n, self=1e3 * self_s / n)
    return out


# -------------------------------------------------------------- the trace
def _trace_file(trace_dir: str) -> str | None:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return paths[-1] if paths else None


@functools.lru_cache(maxsize=2)
def read(trace_dir: str) -> dict:
    """From the newest trace under ``trace_dir``: ``spans``, the program's
    host spans ``(name, start_ns, end_ns)``; and per device index, ``ops``
    (``(name, start_ns, end_ns)``, as ``bench.tracing`` reads them) and
    ``modules`` (the ``XLA Modules`` line's events, alike)."""
    import jax

    out = {"spans": [], "ops": {}, "modules": {}}
    path = _trace_file(trace_dir)
    if path is None:
        return out
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        m = _DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name in (tracing.OPS_LINE, MODULES_LINE):
                key = "ops" if line.name == tracing.OPS_LINE else "modules"
                out[key].setdefault(int(m.group(2)), []).extend(
                    (e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events)
            elif not m:
                out["spans"].extend(
                    (e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events if e.name.startswith(SPAN_PREFIX))
    return out


@functools.lru_cache(maxsize=2)
def scoped_device_ops(trace_dir: str, n_devices: int) -> tuple:
    """``(name, start_ns, end_ns, scope)`` lists of the devices a run used
    (the first ``n_devices`` of ``jax.devices()``, as the harness takes
    them), with the scopes of the programs that ran in the window."""
    import jax

    tr = read(trace_dir)
    devs = [d.id for d in jax.devices()[:n_devices]]
    wanted = {module_name(m[0]) for d in devs
              for m in tr["modules"].get(d, [])}
    programs = held_programs(wanted) if wanted else {}
    return tuple(scoped_ops(tr["ops"].get(d, []), tr["modules"].get(d, []),
                            programs) for d in devs)


def log(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


@functools.lru_cache(maxsize=2)
def _scope_split(trace_dir: str, n_devices: int, lo: float, hi: float):
    per_dev = [scope_ns(ops, lo, hi)
               for ops in scoped_device_ops(trace_dir, n_devices)]
    split = {}
    for tot in per_dev:
        for k, v in tot.items():
            split[k] = split.get(k, 0.0) + 1e-9 * v / len(per_dev)
    if any(k != UNSCOPED for k in split):
        whole = sum(split.values())
        log(f"device seconds by scope over the window: "
            f"{dict(sorted(split.items()))}, under a scope "
            f"{100.0 * (1.0 - split.get(UNSCOPED, 0.0) / whole)!r}%")
    return split


def scope_split(rec) -> dict:
    """Device seconds of the traced window by ``icd.*`` scope (``unscoped``
    for the rest), averaged over the run's devices; empty without a
    trace. Logged once per trace."""
    if not rec.get("ops") or rec.get("window_ns") is None \
            or not rec.get("trace_dir"):
        return {}
    return _scope_split(rec["trace_dir"], len(rec["ops"]), *rec["window_ns"])


def scope_ms_per_step(rec, scopes) -> float | None:
    """Device milliseconds per training step of the ops under ``scopes``;
    None where no op of the window carries an ``icd.*`` scope."""
    split = scope_split(rec)
    if not rec.get("steps") or not any(k != UNSCOPED for k in split):
        return None
    return 1e3 * sum(split.get(s, 0.0) for s in scopes) / rec["steps"]
