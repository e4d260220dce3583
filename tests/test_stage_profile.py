"""The compiled stage kernel's op-name map (``repro.obs.profile``) and the
data plane's spans inside ``stage.acquire`` (``plane.wait``,
``plane.append``, ``plane.prefetch``), on the profiler's clock and absent
with observability off."""
import glob
import os

import jax
import numpy as np
import pytest

from repro.api import (DataSpec, ModelSpec, ObsSpec, OptimizerSpec,
                       PolicySpec, RunSpec, ScheduleSpec, build)
from repro.obs.profile import hlo_op_names

FWD = "jit(kernel)/jvp(bet.forward)/mul"
OPT = "jit(kernel)/bet.optimizer/add"
HLO = f"""HloModule jit_kernel

%fused_computation (param_0: f32[4]) -> f32[4] {{
  %param_0 = f32[4]{{0}} parameter(0)
  ROOT %mul.1 = f32[4]{{0}} multiply(%param_0, %param_0), metadata={{op_name="{FWD}"}}
}}

ENTRY %main.9 (Arg_0.1: f32[4]) -> f32[4] {{
  %Arg_0.1 = f32[4]{{0}} parameter(0)
  %copy-start.1 = (f32[4]{{0}}, f32[4]{{0:S(1)}}, u32[]) copy-start(%Arg_0.1)
  %copy-done.1 = f32[4]{{0:S(1)}} copy-done(%copy-start.1)
  %fusion.1 = f32[4]{{0}} fusion(%copy-done.1), kind=kLoop, calls=%fused_computation
  %add.2 = f32[4]{{0}} add(%fusion.1, %fusion.1), metadata={{op_name="{OPT}"}}
  ROOT %copy.3 = f32[4]{{0}} copy(%add.2)
}}
"""


@pytest.mark.parametrize("inst,names", [
    ("mul.1", (FWD,)),              # its own metadata
    ("add.2", (OPT,)),
    ("fusion.1", (FWD,)),           # a fusion without: its fused ops'
    ("copy.3", (OPT,)),             # a compiler copy: its operand's
    ("copy-done.1", (FWD,)),        # a prefetch of a parameter: its user's
    ("copy-start.1", (FWD,)),
])
def test_hlo_op_names_fall_back_to_fused_ops_operands_then_users(inst,
                                                                 names):
    assert hlo_op_names(HLO)[inst] == names


def _spec(obs: ObsSpec) -> RunSpec:
    return RunSpec(
        name="profile",
        data=DataSpec(kind="lm", plane="plane", seq_len=32, corpus_size=16,
                      eval_rows=2, shard_size=2, seed=5),
        model=ModelSpec(arch="qwen3-0.6b", reduced=True),
        policy=PolicySpec("fixed_steps", {"inner_steps": 2,
                                          "final_steps": 2}),
        optimizer=OptimizerSpec("adamw_lm", {"lr": 1e-3, "batch_size": 2}),
        schedule=ScheduleSpec(n0=4, growth=2, clock={"preloaded": 4},
                              step_cost="batch", wait_on_expand=True,
                              carry_state=True),
        obs=obs)


def _annotations(logdir) -> set:
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    return {e.name for p in ProfileData.from_file(path).planes
            if p.name.startswith("/host:") for line in p.lines
            for e in line.events
            if e.name.startswith(("stage.", "plane."))}


def _run(spec: RunSpec, logdir):
    session = build(spec)
    events = []
    if session.recorder is not None:
        session.recorder.add_listener(events.append)
    ends = []
    session.on_stage(lambda end: ends.append(jax.device_get(end.params)))
    jax.profiler.start_trace(str(logdir))
    try:
        trace = session.run()
    finally:
        jax.profiler.stop_trace()
    return trace, ends[-1], events, _annotations(logdir)


def test_plane_spans_nest_in_acquire_and_obs_off_is_silent_and_identical(
        tmp_path):
    off, params_off, events_off, ann_off = _run(_spec(ObsSpec()),
                                                tmp_path / "off")
    on, params_on, events, ann_on = _run(_spec(ObsSpec(enabled=True)),
                                         tmp_path / "on")
    # observability off: no recorder, and nothing in the profiler's trace
    assert events_off == [] and ann_off == set()
    assert {"stage.acquire", "stage.compute", "plane.wait", "plane.append",
            "plane.prefetch"} <= ann_on
    # the same trajectory, bit for bit
    for col in ("f_window", "f_full"):
        np.testing.assert_array_equal(off.column(col), on.column(col))
    for a, b in zip(jax.tree_util.tree_leaves(params_off),
                    jax.tree_util.tree_leaves(params_on)):
        np.testing.assert_array_equal(a, b)
    spans = [e for e in events if e["kind"] == "span"]
    acquire = [(e["t"], e["t"] + e["dur"]) for e in spans
               if e["name"] == "stage.acquire"]
    plane = [e for e in spans if e["name"].startswith("plane.")]
    assert {e["name"] for e in plane} == {"plane.wait", "plane.append",
                                          "plane.prefetch"}
    for e in plane:
        assert any(s <= e["t"] and e["t"] + e["dur"] <= t
                   for s, t in acquire), e
