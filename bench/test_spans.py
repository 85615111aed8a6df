"""The reduction by the program's spans and scopes (bench/spans.py): idle
time under the innermost span by hand, the recorded v5e trace against
bench/tracing.py, the op-to-scope map of a compiled program, and the new
readers on a program with and without the counters, and bench/breakdown.py
with the two harness calls it stands in for."""
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import spans, tracing
from bench.spec import Spec

TRACE = Path(__file__).parent / "testdata" / "ivf_online_v5e.xplane.pb"


def test_innermost_span_wins():
    s = 1e9
    nested = [(0, 10 * s, "bench.serve_loop"), (1 * s, 9 * s,
                                                "kbest.serve_loop"),
              (2 * s, 5 * s, "kbest.dispatch"),
              (3 * s, 4 * s, "kbest.device_wait"),
              (11 * s, 13 * s, "bench.results")]
    gs, ge = np.array([0.5, 4.5, 12.5]) * s, np.array([3.5, 10.5, 14]) * s
    got = spans.attribute(gs, ge, sorted(nested))
    assert got == pytest.approx({
        "bench.serve_loop": 0.5 + 1.0, "kbest.serve_loop": 1.0 + 4.0,
        "kbest.dispatch": 1.0 + 0.5, "kbest.device_wait": 0.5,
        "bench.results": 0.5, "host.other": 0.5 + 1.0})
    assert sum(got.values()) == pytest.approx(
        float(np.sum(ge - gs)) * 1e-9)


def test_a_program_span_beats_a_bench_span_that_starts_later():
    s = 1e9
    got = spans.attribute(np.array([0.0]), np.array([4 * s]),
                          [(0, 4 * s, "kbest.dispatch"),
                           (1 * s, 2 * s, "bench.wait")])
    assert got == pytest.approx({"kbest.dispatch": 4.0})


def test_recorded_trace_gives_the_same_bench_split():
    old = tracing.reduce_file(str(TRACE))
    new = spans.reduce_file(str(TRACE), {}, old)
    assert dict(new.idle) == pytest.approx(dict(old.gaps))
    # no HLO for its program (`jit_run`): every leaf op is unscoped, and
    # the ops that enclose others are left out
    leaf = sum(t for name, t in old.ops
               if name.split("/%", 1)[1].split(".")[0]
               not in spans.CONTROL_FLOW)
    assert [k for k, _ in new.device] == [spans.UNSCOPED]
    assert new.device[0][1] == pytest.approx(leaf)
    assert new.n_dispatches == 0 and new.n_d2h == 64
    assert spans.reduce_file(str(TRACE), {}, None) is None


def test_instruction_of_a_trace_op():
    assert spans.instruction(
        "%copy-start = (u8[141,256,16]{1,2,0:T(8,128)(4,1)S(1)}, "
        "u32[]{:S(2)}) copy-start(u8[141,256,16]{1,2,0:T(8,128)(4,1)} "
        "%index") == ("copy-start", "copy-start")
    assert spans.instruction(
        "%while.10 = (s32[], f32[8,16]{1,0}) while(%tuple), "
        "condition=%c, body=%b") == ("while.10", "while")
    assert spans.instruction("not an instruction") == ("", "")


def test_scopes_of_a_compiled_program():
    def f(x):
        with jax.named_scope("kbest.expand"):
            y = jnp.sin(x) * 2

        def body(c):
            with jax.named_scope("kbest.queue"):
                return c[0] + 1, jnp.sort(c[1] * 3, axis=1)
        return jax.lax.while_loop(lambda c: c[0] < 3, body, (0, y))[1]

    hlo = jax.jit(f).lower(np.ones((8, 16), np.float32)).compile().as_text()
    got = spans.scopes(hlo)
    ops = {}
    for line in hlo.splitlines():
        name, opcode = spans.instruction(line)
        if name:
            assert got[name][0] == opcode
            ops.setdefault(opcode, []).append(got[name][1])
    assert "kbest.queue" in ops["sort"]
    assert "kbest.expand" in ops["fusion"]
    assert {s for s in sum(ops.values(), []) if s} <= {
        "kbest.expand", "kbest.queue", "kbest.expand+kbest.queue"}


# a fusion whose root is the queue's gather and whose body recomputes the
# expansion's distances: its time belongs to both scopes
MIXED = """HloModule jit_kbest_search_b8, entry_computation_layout={()}

%fused_computation.52 (param_0: f32[8,24], param_1: s32[8,24]) -> f32[192] {
  %param_0 = f32[8,24]{1,0} parameter(0)
  %multiply.3 = f32[8,24]{1,0} multiply(%param_0, %param_0), metadata={op_name="jit(kbest_search_b8)/while/body/kbest.expand/mul"}
  %param_1 = s32[8,24]{1,0} parameter(1)
  %gather.7 = f32[8,24]{1,0} gather(%multiply.3, %param_1), metadata={op_name="jit(kbest_search_b8)/while/body/kbest.queue/gather"}
  ROOT %bitcast.1 = f32[192]{0} bitcast(%gather.7), metadata={op_name="jit(kbest_search_b8)/while/body/kbest.queue/gather"}
}

%fused_computation.53 (param_0.1: f32[8,24]) -> f32[8,24] {
  %param_0.1 = f32[8,24]{1,0} parameter(0)
  ROOT %negate.2 = f32[8,24]{1,0} negate(%param_0.1), metadata={op_name="jit(kbest_search_b8)/while/body/kbest.queue/neg"}
}

ENTRY %main (p0: f32[8,24], p1: s32[8,24]) -> f32[192] {
  %p0 = f32[8,24]{1,0} parameter(0)
  %p1 = s32[8,24]{1,0} parameter(1)
  %fusion.53 = f32[8,24]{1,0} fusion(%p0), kind=kLoop, calls=%fused_computation.53
  %copy.9 = f32[8,24]{1,0} copy(%fusion.53)
  ROOT %fusion.52 = f32[192]{0} fusion(%copy.9, %p1), kind=kLoop, calls=%fused_computation.52, metadata={op_name="jit(kbest_search_b8)/while/body/kbest.queue/gather"}
}
"""


def test_a_fusion_of_two_scopes_is_labelled_with_both():
    got = spans.scopes(MIXED)
    assert got["fusion.52"] == ("fusion", "kbest.expand+kbest.queue")
    # a fusion without an op_name of its own takes its body's scope
    assert got["fusion.53"] == ("fusion", "kbest.queue")
    assert got["copy.9"] == ("copy", None)
    ops = [("jit_kbest_search_b8(7)/%fusion.52", 3.0),
           ("jit_kbest_search_b8(7)/%fusion.53", 1.0),
           ("jit_kbest_search_b8(7)/%copy.9", 0.5),
           ("jit_kbest_search_b8(7)/%while.4", 9.0),
           ("jit_other(8)/%fusion.1", 0.25),
           ("jit_other(8)/%while.2", 7.0)]
    assert spans.by_scope(ops, {"jit_kbest_search_b8": MIXED}) == [
        ("kbest.expand+kbest.queue", 3.0), ("kbest.queue", 1.0),
        (spans.UNSCOPED, 0.75)]


def _ctx(**stats):
    return SimpleNamespace(stats=SimpleNamespace(**stats))


@pytest.mark.parametrize("metric", ["engine_host_ms.bulk",
                                    "pad_share.online", "lockstep_util.bulk"])
def test_new_readers_read_nothing_from_a_program_without_them(metric):
    """The parent's program keeps no such counters or scopes: each reader
    returns None and raises nothing."""
    ctx = _ctx(n_requests=4, n_queries=10, lat_p50_ms=3.0)
    assert Spec().reader(metric)(ctx) is None


def test_new_readers():
    spec = Spec()
    ctx = _ctx(n_requests=2, n_queries=3, host_p50_ms=1.5, rows_padded=5,
               hops=30, lane_slots=40)
    assert spec.reader("engine_host_ms.bulk")(ctx) == 1.5
    assert spec.reader("pad_share.online")(ctx) == 5 / 8
    assert spec.reader("lockstep_util.bulk")(ctx) == 0.75


def test_harness_calls_the_window_and_the_reduction_through_modules(
        monkeypatch):
    """bench/breakdown.py replaces `drive.run_window` and
    `tracing.reduce_dir` for its run; that holds only while harness.run
    calls both through their modules."""
    from bench import drive, harness
    calls = []
    run_window, reduce_dir = drive.run_window, tracing.reduce_dir

    def window(*args, **kw):
        calls.append("window")
        return run_window(*args, **kw)

    def reduce(trace_dir):
        calls.append("reduce")
        return reduce_dir(trace_dir)

    monkeypatch.setattr(drive, "run_window", window)
    monkeypatch.setattr(tracing, "reduce_dir", reduce)
    spec = Spec()
    cell = harness.load(spec, "deep1m_ivf_pq8.bulk", n=2000, pool=400)
    harness.run(spec, cell, seed=2**31 + 12, seconds=0.5, trace=True,
                rehearse=True)
    assert calls == ["window", "reduce"]


def test_breakdown_run_logs_the_engine_and_restores_the_harness(capsys):
    """bench/breakdown.py's run is harness.run with the engine's spans
    logged after the window; it leaves the harness as it found it."""
    from bench import breakdown, drive, harness
    spec = Spec()
    cell = harness.load(spec, "deep1m_ivf_pq8.bulk", n=2000, pool=400)
    before = (drive.run_window, tracing.reduce_dir)
    out = breakdown.run(spec, cell, seed=2**31 + 11, seconds=1.0,
                        trace=True, rehearse=True)
    assert (drive.run_window, tracing.reduce_dir) == before
    assert out["correct"]
    err = capsys.readouterr().err
    for name in ("kbest.serve_loop", "kbest.dispatch", "kbest.pad",
                 "kbest.enqueue", "kbest.device_wait", "kbest.fetch"):
        assert f"span {name}: n=" in err
    assert "counters n_queries=" in err
    assert "engine_host_ms.bulk" in out["metrics"]
