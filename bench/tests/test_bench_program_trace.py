"""The readers of the program's own spans and scopes in the trace
(``bench/program_trace.py`` and the metrics that use it), on a small
recorded excerpt: program spans beside the benchmark's, device ops whose
``icd.*`` scopes the compiled program gives; and the spans and compiled
programs that a real run on the CPU leaves."""
import contextlib
import gc
import random

import jax
import jax.numpy as jnp
import pytest

from bench import program_trace, run, tracing

# One window of a serving run: two flushes with their phases, the
# benchmark's spans around the loop's calls, and the device's ops.
WINDOW = (0, 4000)
BENCH_SPANS = [
    ("bench.window", 0, 4000),
    ("bench.step", 900, 2100),
    ("bench.submit", 2950, 4000),
]
PROGRAM_SPANS = [
    ("repro.request", 500, 2000),
    ("repro.flush", 1000, 2000),
    ("repro.assemble", 1000, 1100),
    ("repro.transfer", 1100, 1200),
    ("repro.dispatch", 1200, 1300),
    ("repro.wait", 1300, 1900),
    ("repro.route", 1900, 2000),
    ("repro.flush", 3000, 3500),
    ("repro.wait", 3050, 3450),
]
KERNEL = "%topk_score.1 = (f32[8,128]{1,0}, s32[8,128]{1,0}) custom-call()"
SERVE_OPS = [
    (KERNEL, 1250, 1600),     # busy inside the first flush
    ("%copy-done.1 = s32[8,128]{1,0} copy-done(%copy-start.1)", 1900, 2000),
    (KERNEL, 3100, 3500),     # and the second
]

# One window of two training steps: ops named as a TPU trace names them,
# their scopes found by instruction name in the program that ran them.
def fusion(n, shape="f32[20000000]{0:T(1024)}"):
    return f"%fusion.{n} = {shape} fusion(f32[200000]{{0}} %a), kind=kLoop"


TRAIN_OPS = [
    (fusion(89), 0, 3000),                    # a column gather
    (fusion(61, "f32[68000]{0}"), 3000, 5000),   # a segment sum
    (fusion(70), 5000, 5500),                 # the residual patch
    (fusion(82), 5500, 6000),                 # the permutation
    (fusion(90, "f32[200000]{0}"), 6000, 6200),  # the Newton step
    ("%copy.50 = f32[200000,128]{1,0} copy(%w)", 6200, 6300),
    (fusion(89), 6300, 9300),                 # the next step's gather
]
MODULES = [("jit_epoch(6536856975076015183)", 0, 9400)]
PROGRAM = {"jit_epoch": {"fusion.89": "icd.gather", "fusion.61": "icd.segsum",
                         "fusion.70": "icd.patch", "fusion.82": "icd.permute",
                         "fusion.90": "icd.newton", "copy.50": None}}


@pytest.fixture(autouse=True)
def fresh_caches():
    for f in (program_trace.read, program_trace.scoped_device_ops,
              program_trace._scope_split):
        f.cache_clear()
    yield
    for f in (program_trace.read, program_trace.scoped_device_ops,
              program_trace._scope_split):
        f.cache_clear()


def recorded(monkeypatch, spans=(), ops=(), modules=(), programs=None):
    dev = jax.devices()[0].id
    monkeypatch.setattr(program_trace, "read", lambda d: {
        "spans": list(spans), "ops": {dev: list(ops)},
        "modules": {dev: list(modules)}})
    monkeypatch.setattr(program_trace, "held_programs",
                        lambda wanted: {k: v for k, v in (programs or {}).items()
                                        if k in wanted})


def metric(name):
    return run.load_module(run.BENCH / "metrics" / f"{name}.py")


# ------------------------------------------------------------ serving side
def test_flush_idle_counts_only_the_idle_inside_flushes(monkeypatch):
    recorded(monkeypatch, spans=PROGRAM_SPANS)
    rec = {"ops": [SERVE_OPS], "window_ns": WINDOW, "trace_dir": "t",
           "spans": BENCH_SPANS, "program_spans": []}
    # first flush: 1000 ns, 450 busy; second: 500 ns, 400 busy; the
    # 1000 ns between them is light load and does not count
    assert metric("serve.flush_idle_ms").read(rec) == pytest.approx(
        1e-6 * (550 + 100) / 2)


def test_flush_idle_reads_nothing_without_program_spans(monkeypatch):
    recorded(monkeypatch, spans=[])
    rec = {"ops": [SERVE_OPS], "window_ns": WINDOW, "trace_dir": "t",
           "spans": BENCH_SPANS}
    assert metric("serve.flush_idle_ms").read(rec) is None
    assert metric("serve.flush_idle_ms").read({"ops": []}) is None


def test_gaps_are_labelled_by_either_prefix():
    gaps = program_trace.labelled_gaps(SERVE_OPS, BENCH_SPANS + PROGRAM_SPANS,
                                       *WINDOW)
    by_start = {a: lab for lab, a, _ in gaps}
    assert by_start == {0: "repro.request", 1600: "repro.wait",
                        2000: "host", 3500: "bench.submit"}
    assert [ns for _, _, ns in gaps] == sorted(
        (ns for _, _, ns in gaps), reverse=True)
    long = program_trace.labelled_gaps(SERVE_OPS, PROGRAM_SPANS, *WINDOW,
                                       min_ns=400)
    assert [(lab, a) for lab, a, _ in long] == [
        ("repro.request", 0), ("host", 2000), ("host", 3500)]


def test_innermost_matches_the_gap_rule():
    rng = random.Random(7)
    spans = [(f"repro.s{i}", a, a + rng.randint(1, 300))
             for i, a in enumerate(rng.randint(0, 1000) for _ in range(60))]
    spans.append(("bench.window", 0, 1400))
    points = [rng.uniform(0, 1400) for _ in range(200)]
    assert program_trace.innermost(points, spans) == [
        tracing.label_gap((p, p), spans) for p in points]


def test_flush_phases_and_self_time():
    from repro.obs.trace import Tracer

    clock = {"t": 0.0}
    tr = Tracer(clock=lambda: clock["t"])
    fl = tr.begin("flush", parent=None)
    for name, dt in (("assemble", 1e-3), ("wait", 2e-3)):
        clock["t"] += 0.5e-3            # 0.5 ms outside any phase
        sp = tr.begin(name, parent=fl)
        clock["t"] += dt
        tr.end(sp)
    tr.end(fl)
    got = program_trace.flush_phases(tr.spans)
    assert got == pytest.approx({"assemble": 1.0, "wait": 2.0,
                                 "flush": 4.0, "self": 1.0})
    assert program_trace.flush_phases([]) == {}


# ----------------------------------------------------------- training side
def train_rec():
    return {"ops": [[op for op in TRAIN_OPS]], "window_ns": (0, 9400),
            "trace_dir": "t", "steps": 2}


def test_gather_reads_gather_patch_and_permute(monkeypatch):
    recorded(monkeypatch, ops=TRAIN_OPS, modules=MODULES, programs=PROGRAM)
    # (3000 + 3000 gathers + 500 patch + 500 permute) ns over 2 steps
    assert metric("train.gather_ms").read(train_rec()) == pytest.approx(
        1e-6 * 7000 / 2)


def test_segsum_and_the_split(monkeypatch):
    recorded(monkeypatch, ops=TRAIN_OPS, modules=MODULES, programs=PROGRAM)
    assert metric("train.segsum_ms").read(train_rec()) == pytest.approx(
        1e-6 * 2000 / 2)
    split = program_trace.scope_split(train_rec())
    assert split["unscoped"] == pytest.approx(100e-9)
    assert split["icd.newton"] == pytest.approx(200e-9)


def test_unscoped_program_reads_nothing(monkeypatch):
    recorded(monkeypatch, ops=TRAIN_OPS, modules=MODULES,
             programs={"jit_epoch": dict.fromkeys(PROGRAM["jit_epoch"])})
    assert metric("train.gather_ms").read(train_rec()) is None
    assert metric("train.segsum_ms").read(train_rec()) is None
    assert metric("train.segsum_ms").read({"steps": 2}) is None


HLO = """HloModule jit_epoch, entry_computation_layout={()->f32[8]}

%fused_computation.1 (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  %mul.1 = f32[8]{0} multiply(%param_0, %param_0), metadata={op_name="jit(epoch)/icd.newton/mul"}
  ROOT %add.2 = f32[8]{0} add(%param_0, %mul.1)
}

%fused_computation.2 (param_0.1: f32[8]) -> f32[8] {
  %param_0.1 = f32[8]{0} parameter(0)
  ROOT %gather.3 = f32[8]{0} gather(%param_0.1), metadata={op_name="jit(epoch)/icd.gather/jit(_take)/gather"}
}

ENTRY %main.9 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  %fusion.1 = f32[8]{0} fusion(%p), kind=kLoop, calls=%fused_computation.1
  %fusion.2 = f32[8]{0} fusion(%fusion.1), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(epoch)/icd.patch/mul"}
  ROOT %copy.5 = f32[8]{0} copy(%fusion.2)
}
"""


def test_hlo_scopes_take_a_fusions_root():
    got = program_trace.hlo_scopes(HLO)
    assert got["fusion.1"] == "icd.newton"   # root left without metadata
    assert got["fusion.2"] == "icd.patch"    # its own metadata comes first
    assert got["gather.3"] == "icd.gather" and got["copy.5"] is None
    assert program_trace.instr_name(fusion(89)) == "fusion.89"
    assert program_trace.scope_of("jit(f)/icd.gather/icd.patch/x") \
        == "icd.patch"


def _step(scoped: bool):
    """One jitted step; with ``scoped`` its phases carry ``icd.*`` scopes,
    which leave its compiled program and the cache key unchanged."""
    def scope(name):
        return jax.named_scope(name) if scoped else contextlib.nullcontext()

    @jax.jit
    def step(x, i):
        with scope("icd.gather"):
            y = jnp.take(x, i)
        with scope("icd.segsum"):
            return jax.ops.segment_sum(y * 2.0, i, 16)

    step(jnp.ones(64), jnp.arange(64) % 16).block_until_ready()
    return step                 # its compiled program lives while it does


@pytest.fixture
def persistent_cache(tmp_path):
    """JAX's persistent compilation cache in ``tmp_path``, every program
    cached; turned off again afterwards."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    before = {n: getattr(jax.config, n) for n in names}
    jax.config.update(names[0], str(tmp_path / "cache"))
    jax.config.update(names[1], 0)
    jax.config.update(names[2], -1)
    cc.reset_cache()
    yield
    for n, v in before.items():
        jax.config.update(n, v)
    cc.reset_cache()


def held_scopes():
    prog = program_trace.held_programs({"jit_step"})["jit_step"]
    return {s for s in prog.values() if s}


def test_held_programs_find_a_compiled_step():
    step = _step(scoped=True)
    assert held_scopes() >= {"icd.gather", "icd.segsum"}
    del step


def test_held_programs_see_past_an_older_builds_cache_entry(
        persistent_cache):
    old = _step(scoped=False)   # an older build writes the cache entry
    new = _step(scoped=True)    # this build loads it, metadata and all
    held = [c._executable for c in gc.get_objects()
            if type(c).__name__ == "MeshComputation"
            and c._executable is not None and "icd." in
            c._hlo.operation.get_asm(enable_debug_info=True)]
    assert held and all("icd." not in e.xla_extension_executable()
                        .hlo_modules()[0].to_string() for e in held)
    assert held_scopes() >= {"icd.gather", "icd.segsum"}
    del old, new


def test_read_finds_the_program_spans_of_a_cpu_trace(tmp_path):
    from repro.obs.trace import Tracer

    tr = Tracer()
    with tracing.Window(str(tmp_path / "t"), lambda: 0) as win:
        fl = tr.begin("flush", parent=None, batch=3)
        with tr.activate(fl):
            with tr.span("wait"):
                _step(scoped=True)
        tr.end(fl)
        win.close()
    got = program_trace.read(str(tmp_path / "t"))
    names = [n for n, _, _ in got["spans"]]
    assert names.count("repro.flush") == 1 and names.count("repro.wait") == 1
    (_, fa, fb), = [s for s in got["spans"] if s[0] == "repro.flush"]
    (_, wa, wb), = [s for s in got["spans"] if s[0] == "repro.wait"]
    assert fa <= wa <= wb <= fb
    assert got["ops"] == {}      # the CPU has no device plane
