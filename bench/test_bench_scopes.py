"""The stage kernel's device time by scope: the program's op-name map of a
small compiled LM stage kernel, the benchmark's rule that classes it, the
metric readers on a synthetic record, and the program's spans on the
profiler's clock (CPU)."""
from __future__ import annotations

import pathlib
import sys
import time

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness, scopes, xplane  # noqa: E402
from bench.compile_clock import CompileClock  # noqa: E402

CELL = "qwen3-0.6b.bet-lm"


@pytest.fixture(scope="module")
def lm_run():
    """A tiny BET LM job with observability on: its stage kernels are in
    the program's registry."""
    from repro.api import (DataSpec, ModelSpec, ObsSpec, OptimizerSpec,
                           PolicySpec, RunSpec, ScheduleSpec, build)
    from repro.obs import profile
    profile.STAGE_KERNELS.clear()
    spec = RunSpec(
        name="scopes",
        data=DataSpec(kind="lm", plane="plane", seq_len=32, corpus_size=16,
                      eval_rows=2, shard_size=2, seed=3),
        model=ModelSpec(arch="qwen3-0.6b", reduced=True),
        policy=PolicySpec("fixed_steps", {"inner_steps": 2,
                                          "final_steps": 2}),
        optimizer=OptimizerSpec("adamw_lm", {"lr": 1e-3, "batch_size": 2}),
        schedule=ScheduleSpec(n0=4, growth=2, clock={"preloaded": 4},
                              step_cost="batch", wait_on_expand=True,
                              carry_state=True),
        obs=ObsSpec(enabled=True))
    build(spec).run()


def test_map_of_a_compiled_lm_stage_kernel_holds_every_scope(lm_run):
    from repro.obs import profile
    clock = CompileClock()
    before = clock.snapshot()
    maps = profile.stage_op_names()
    # each executable is found where the run compiled it
    assert clock.snapshot()["backend_compiles"] == before["backend_compiles"]
    assert maps
    classes = scopes.scope_map(maps)
    assert set(scopes.SCOPES) <= set(classes.values())
    backward = [names for m in maps for names in m.values()
                if names and all("transpose(jvp(bet.forward))" in n
                                 for n in names)]
    assert backward and all(scopes.instruction_scope(n) == "backward"
                            for n in backward)


@pytest.mark.parametrize("op_name,scope", [
    ("jit(kernel)/while/body/closed_call/jvp(bet.forward)/while/body/"
     "dot_general", "forward"),
    ("jit(kernel)/while/body/closed_call/transpose(jvp(bet.forward))/"
     "while/body/dot_general", "backward"),
    ("jit(kernel)/while/body/closed_call/transpose(jvp(bet.forward))/"
     "while/body/checkpoint/rematted_computation/dot_general", "backward"),
    ("jit(kernel)/while/body/closed_call/bet.optimizer/mul", "optimizer"),
    ("jit(kernel)/while/body/closed_call/bet.probe/while/body/dot_general",
     "probe"),
    ("jit(kernel)/while/body/bet.batch/gather", "batch"),
    ("jit(kernel)/while/body/closed_call/add", None),
])
def test_scope_of_an_op_name(op_name, scope):
    assert scopes.scope_of(op_name) == scope


def test_conflicting_or_mixed_instructions_are_unscoped():
    fwd = "jit(kernel)/jvp(bet.forward)/dot_general"
    opt = "jit(kernel)/bet.optimizer/mul"
    maps = [{"fusion.1": (fwd,), "fusion.2": (fwd,), "fusion.3": (fwd, opt)},
            {"fusion.1": (fwd,), "fusion.2": (opt,)}]
    assert scopes.scope_map(maps) == {"fusion.1": "forward",
                                      "fusion.2": None, "fusion.3": None}
    got = scopes.scope_seconds({"fusion.1": 1.0, "fusion.2": 0.5,
                                "fusion.3": 0.25, "other.1": 8.0}, maps)
    assert got["forward"] == 1.0 and got["unscoped"] == 0.75
    assert sum(got.values()) == 1.75        # another program's op: left out


def _record(**kw):
    red = xplane.Reduction(
        window_s=10.0, busy_s=[9.0], collective_s=[0.0],
        op_seconds={"fusion.1": 0.4, "fusion.2": 1.0, "fusion.3": 0.1,
                    "fusion.4": 0.3, "fusion.5": 0.02, "copy.1": 0.05,
                    "dynamic-update-slice.1": 0.01},
        module_seconds={"jit_kernel": 1.87}, idle_gaps=[],
        devices=["/device:TPU:0"])
    spans = [("stage.begin", 1.0, 0.0), ("plane.wait", 1.1, 0.004),
             ("stage.begin", 2.0, 0.0), ("plane.wait", 2.1, 0.002)]
    args = dict(setup_s=1.0, window_s=10.0, attempted=4, failed=0,
                end_to_end={}, checks=[],
                device={"kind": "TPU v5 lite", "count": 1},
                compile_setup={"compile_s": 1.0}, compile_window={},
                counts={"steps_in_window": 4}, reduction=red, spans=spans)
    args.update(kw)
    return harness.Record(**args)


_MAP = {"fusion.1": ("jit(kernel)/jvp(bet.forward)/dot_general",),
        "fusion.2": ("jit(kernel)/transpose(jvp(bet.forward))/dot_general",),
        "fusion.3": ("jit(kernel)/bet.optimizer/mul",),
        "fusion.4": ("jit(kernel)/bet.probe/dot_general",),
        "fusion.5": ("jit(kernel)/bet.batch/gather",),
        "copy.1": ()}
NEW = {"forward_ms.lm": 100.0, "backward_ms.lm": 250.0,
       "optimizer_ms.lm": 25.0, "probe_ms.lm": 75.0, "plane_wait_ms.lm": 3.0}


@pytest.mark.parametrize("name", sorted(NEW))
def test_metric_reader_on_a_synthetic_record(name, monkeypatch):
    from repro.obs import profile
    seen = []
    monkeypatch.setattr(profile, "stage_op_names",
                        lambda since=None: seen.append(since) or [_MAP])
    values = harness.per_layer_values(harness.load_cell(CELL), _record())
    assert values[name] == {"value": pytest.approx(NEW[name]), "unit": "ms"}
    if name != "plane_wait_ms.lm":
        assert seen == [1.0]     # the executables run in the window


@pytest.mark.parametrize("name", sorted(NEW))
def test_metric_reader_leaves_out_what_a_program_lacks(name, monkeypatch):
    from repro.obs import profile
    monkeypatch.delattr(profile, "stage_op_names")   # an older program
    rec = _record(spans=[("stage.begin", 1.0, 0.0),
                         ("stage.acquire", 1.0, 0.05)])
    values = harness.per_layer_values(harness.load_cell(CELL), rec)
    assert name not in values and "acquire_ms.lm" in values


def test_span_annotation_agrees_with_the_listener_copy(tmp_path):
    """A program span is a TraceAnnotation in a captured trace; its copy
    from the recorder's listener, put on the trace's clock through the
    benchmark's sync annotation, lands within a millisecond of it."""
    import jax
    from repro.obs import EventRecorder
    rec = EventRecorder()
    copies = []
    rec.add_listener(lambda ev: copies.append((ev["name"], ev["t"],
                                               ev["dur"])))
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation(xplane.SYNC):
            sync_perf = time.perf_counter()
        for name in ("stage.acquire", "plane.wait"):
            with rec.span(name):
                time.sleep(0.02)
        with jax.profiler.TraceAnnotation(xplane.CLOSE):
            pass
    finally:
        jax.profiler.stop_trace()
    _, host = xplane.read(xplane.find_xplane(str(tmp_path)))
    sync_ns = min(s for n, s, _ in host if n == xplane.SYNC)
    for name, t, dur in copies:
        (s, e), = [(s, e) for n, s, e in host if n == name]
        start = sync_ns + (t - sync_perf) * 1e9
        assert abs(s - start) < 1e6
        assert abs(e - (start + dur * 1e9)) < 1e6
