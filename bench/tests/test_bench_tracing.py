"""The reduction from a trace to busy time, idle share, kernel time and the
breakdown, on a small trace."""
import pytest

from bench import tracing

# (name, start_ns, end_ns) of one device, and the host's benchmark spans
# device op names as a TPU trace gives them (HLO text)
TOPK = ("%topk_score.1 = (f32[64,128]{1,0:T(8,128)}, s32[64,128]{1,0:T(8,128)})"
        " custom-call(s32[1,2]{1,0} %x, f32[15003648,128]{1,0} %pad.0)")
OPS = [
    ("%fusion.1 = f32[20000000]{0:T(1024)} fusion(f32[200000]{0} %a)", 100, 300),
    (TOPK, 250, 650),                     # overlaps fusion.1
    ("%fusion.2 = f32[68000]{0:T(1024)} fusion(f32[68000]{0} %b)", 900, 1000),
    ("%pad.0 = f32[15003648,128]{1,0} pad(f32[15000000,128]{1,0} %psi)",
     1500, 1600),
    ("%fusion.1 = f32[20000000]{0:T(1024)} fusion(f32[200000]{0} %a)",
     1950, 2100),                         # runs past the window
]
SPANS = [
    ("bench.window", 0, 2000),
    ("bench.step", 0, 1300),
    ("bench.submit", 600, 900),
    ("bench.warmup", -500, -100),
]
LO, HI = 0, 2000


def test_busy_is_the_union_inside_the_window():
    # [100, 650] ∪ [900, 1000] ∪ [1500, 1600] ∪ [1950, 2000]
    assert tracing.busy_ns(OPS, LO, HI) == 550 + 100 + 100 + 50


def test_idle_gaps_cover_the_rest():
    gaps = tracing.idle_gaps(OPS, LO, HI)
    assert gaps == [(0, 100), (650, 900), (1000, 1500), (1600, 1950)]
    assert sum(b - a for a, b in gaps) + tracing.busy_ns(OPS, LO, HI) == HI


def test_kernel_time_by_name():
    assert tracing.kernel_ns(OPS, "topk_score", LO, HI) == 400
    assert tracing.kernel_ns(OPS, "fusion", LO, HI) == 200 + 100 + 50
    assert tracing.kernel_ns(OPS, "pad", LO, HI) == 100
    # an operand named after the kernel does not make an op the kernel
    assert tracing.kernel_ns(OPS, "psi", LO, HI) == 0


def test_gaps_are_labelled_by_the_innermost_host_span():
    assert tracing.label_gap((650, 900), SPANS) == "bench.submit"
    assert tracing.label_gap((1000, 1500), SPANS) == "bench.step"
    assert tracing.label_gap((1600, 1950), SPANS) == "host"


def test_breakdown_sums_each_op_and_sorts():
    brk = tracing.breakdown(OPS, SPANS, LO, HI)
    ops = dict(brk["device_ops"])
    assert brk["device_ops"][0] == [
        "topk_score.1 = (f32[64,128]", pytest.approx(400e-9)]
    assert ops["fusion.1 = f32[20000000]"] == pytest.approx(250e-9)
    assert ops["fusion.2 = f32[68000]"] == pytest.approx(100e-9)
    assert brk["idle_gaps"][0] == ["bench.step", pytest.approx(500e-9)]
    assert len(brk["idle_gaps"]) == 4


def test_window_of():
    assert tracing.window_of(SPANS) == (0, 2000)
    assert tracing.window_of(SPANS[1:]) is None


def test_window_spans_reach_the_profilers_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: x @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    with tracing.Window(str(tmp_path / "t"), lambda: 0) as win:
        for _ in range(3):
            with tracing.annotate("step"):
                f(x).block_until_ready()
        win.close()
    assert win.compiles_inside == 0 and win.seconds > 0
    _, spans = tracing.read_trace(str(tmp_path / "t"))
    lo, hi = tracing.window_of(spans)
    steps = [s for s in spans if s[0] == "bench.step"]
    assert len(steps) == 3 and all(lo <= a <= b <= hi for _, a, b in steps)
