#!/usr/bin/env python3
"""Bring-up smoke run of BET's main paths on a TPU, in one process.

    python3 chip_smoke.py                # phases A, B, C on one chip
    python3 chip_smoke.py --four-chips   # only the 4-chip distributed phase

Every phase goes through the front door, ``repro.api.build(RunSpec) ->
Session.run()``:

* A — the paper's Eq. 1 loop: Algorithm 2 (``two_track``) with
  sub-sampled Newton-CG on ``webspam_like`` scaled so that X holds 1 GiB
  on the chip.  The objective must fall, with at most one host transfer
  per stage.
* B — qwen3-0.6b at its published config (28 layers, d_model 1024, vocab
  151936) trained by the ``fixed_steps`` BET schedule for 3 stages at
  batch 8 × seq 1024; the loss must be finite and fall.
* C — falcon-mamba-7b at its published widths, cut to 2 layers, trained
  through the ``mamba`` family with the ``ssm_scan`` Pallas kernel
  compiled: the kernel is dispatched, ``tpu_custom_call`` is in the
  compiled stage's HLO, and the kernel's forward agrees with
  ``kernels/ref.ssm_scan`` at falcon-mamba widths.

``--four-chips`` runs the distributed convex BET of ``bench_dist`` over 4
simulated hosts, one per chip, against the single-host run of the same
spec: each lane's window must sit on its own chip and the trajectories
must agree within ``rel <= 1e-3`` (psum re-associates the fp32 reduction).

Lines before the last are bring-up observations (compile and step seconds,
``peak_bytes_in_use``), not benchmark numbers.  The last line is one JSON
object naming the device; it is printed only when every phase passed.
Without a TPU the script exits non-zero before running anything.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time
import traceback

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax                                                     # noqa: E402
import jax.numpy as jnp                                        # noqa: E402
import numpy as np                                             # noqa: E402

from repro.api import (DataSpec, ModelSpec, OptimizerSpec,     # noqa: E402
                       PolicySpec, RunSpec, ScheduleSpec, TopologySpec,
                       build, convex_problem)
from repro.kernels import ops, ref                             # noqa: E402
from repro.launch.cache import use_compile_cache               # noqa: E402
from repro.obs import profile                                  # noqa: E402

# kernel forward vs kernels/ref.ssm_scan, as a fraction of max |ref|.  Both
# run the same f32 recurrence in the same order; they differ only in the
# exp implementation (Mosaic vs XLA, a few f32 ulps) and the order of the
# N-term sum, both far below 1e-3.  bf16 outputs may further differ by one
# rounding step, 2^-7 of the value; 1e-2 covers that.  A misaligned tile
# or a wrong index gives errors of order 1.
SSM_TOL = {"float32": 1e-3, "bfloat16": 1e-2}
REL_TOL_4 = 1e-3            # bench_dist's psum-reassociation bound


def observe(msg: str) -> None:
    print(f"[observation] {msg}", flush=True)


def peak_bytes() -> int | None:
    """The device's peak since the process started (JAX cannot reset it)."""
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling (a persistent-cache
    load counts as a compile), summed from its own duration events."""
    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event in self.EVENTS:
            self.seconds += duration


def _lm_spec(model: ModelSpec, *, batch: int, seq_len: int, steps: int,
             stages: int, eval_rows: int) -> RunSpec:
    """The CLI's LM spec (launch/train.py) on the streaming plane, whose
    fixed-capacity window keeps the stage kernel's shapes across stages:
    only the first stage compiles."""
    n0 = 4 * batch
    return RunSpec(
        name="smoke_lm",
        data=DataSpec(kind="lm", plane="plane", seq_len=seq_len,
                      corpus_size=n0 * 2 ** (stages - 1),
                      eval_rows=eval_rows, shard_size=batch),
        model=model,
        policy=PolicySpec("fixed_steps", {"inner_steps": steps,
                                          "final_steps": steps}),
        optimizer=OptimizerSpec("adamw_lm", {"lr": 1e-3,
                                             "batch_size": batch}),
        schedule=ScheduleSpec(n0=n0, clock={"preloaded": n0},
                              step_cost="batch", wait_on_expand=True,
                              carry_state=True))


def _train_lm(spec: RunSpec, profiler=None) -> dict:
    """Run the LM session; stage 0 includes the compile, later stages are
    steady (timed between stage boundaries, after block_until_ready)."""
    t0 = time.perf_counter()
    session = build(spec)
    build_s = time.perf_counter() - t0
    if profiler is not None:
        session.engine.profiler = profiler
    stamps = []

    def stamp(end):
        jax.block_until_ready(end.params)
        stamps.append((time.perf_counter(), end.step_count))

    session.on_stage(stamp)
    t0 = time.perf_counter()
    trace = session.run()
    losses = np.asarray(trace.column("f_window"), np.float64)
    starts = [(t0, 0)] + stamps[:-1]
    per_stage = [(t - s, n - m) for (t, n), (s, m) in zip(stamps, starts)]
    steady = [dt / k for dt, k in per_stage[1:] if k]
    return {"losses": losses, "stages": trace.meta["stages"],
            "build_s": build_s, "first_stage_s": per_stage[0][0],
            "step_s": float(np.median(steady)) if steady else None}


def _check_lm(name: str, res: dict, *, falls: bool) -> None:
    losses = res["losses"]
    observe(f"{name}: stages={res['stages']} steps={len(losses)} "
            f"build_s(params+data)={res['build_s']:.3f} "
            f"first_stage_s(compile+steps)={res['first_stage_s']:.3f} "
            f"steady_step_s={res['step_s']} loss {losses[0]:.4f} -> "
            f"{losses[-1]:.4f}")
    if res["stages"] < 2:
        raise AssertionError(f"{name}: ran {res['stages']} stage(s), need 2+")
    if not np.all(np.isfinite(losses)):
        raise AssertionError(f"{name}: non-finite loss {losses}")
    if falls and not losses[-1] < losses[0]:
        raise AssertionError(f"{name}: loss did not fall: {losses}")


# --------------------------------------------------------------- phases
def phase_a(*, scale: float = 16.0, n0: int = 16384,
            min_bytes: int = 1 << 30) -> dict:
    """Eq. 1 two-track BET; run twice, the second time on warm kernels."""
    spec = RunSpec(
        name="smoke_convex",
        data=DataSpec(dataset="webspam_like", scale=scale, lam=1e-3),
        policy=PolicySpec("two_track", {"final_steps": 10}),
        optimizer=OptimizerSpec("newton_cg", {"hessian_fraction": 0.2}),
        schedule=ScheduleSpec(n0=n0))
    t0 = time.perf_counter()
    session = build(spec)
    x_bytes = session.problem.X.nbytes
    build_s = time.perf_counter() - t0
    if x_bytes < min_bytes:
        raise AssertionError(f"A: X holds {x_bytes} B < {min_bytes} B")
    walls = []
    for run in range(2):
        if run:
            session = build(spec)
        t0 = time.perf_counter()
        trace = session.run()
        jax.block_until_ready(trace.params)
        walls.append(time.perf_counter() - t0)
    f = np.asarray(trace.column("f_full"), np.float64)
    transfers, stages = trace.meta["host_transfers"], trace.meta["stages"]
    observe(f"A: X {tuple(session.problem.X.shape)} = {x_bytes} B, "
            f"build_s(data+upload)={build_s:.3f} run_s cold={walls[0]:.3f} "
            f"warm={walls[1]:.3f} steps={len(f)} stages={stages} "
            f"host_transfers={transfers} f {f[0]:.6f} -> {f[-1]:.6f}")
    if not (np.all(np.isfinite(f)) and f[-1] < f[0]):
        raise AssertionError(f"A: objective did not fall: {f[0]} -> {f[-1]}")
    if transfers > stages:
        raise AssertionError(f"A: {transfers} host transfers > {stages} stages")
    return {"f": f, "transfers": transfers, "stages": stages}


def phase_b(*, model: ModelSpec = ModelSpec(arch="qwen3-0.6b",
                                            reduced=False),
            batch: int = 8, seq_len: int = 1024, steps: int = 4,
            stages: int = 3, eval_rows: int = 8) -> dict:
    res = _train_lm(_lm_spec(model, batch=batch, seq_len=seq_len,
                             steps=steps, stages=stages,
                             eval_rows=eval_rows))
    _check_lm(f"B {model.arch}", res, falls=True)
    return res


def ssm_parity(shape=(1, 2048, 8192, 16), dtype=jnp.float32) -> float:
    """max |ops.ssm_scan - ref.ssm_scan| / max |ref| on random inputs."""
    B, S, di, N = shape
    ks = jax.random.split(jax.random.key(7), 4)
    u = jax.random.normal(ks[0], (B, S, di), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, di))).astype(dtype)
    Bs = jax.random.normal(ks[2], (B, S, N), dtype)
    Cs = jax.random.normal(ks[3], (B, S, N), dtype)
    A_log = jnp.log(jnp.tile(jnp.arange(1, N + 1, dtype=jnp.float32), (di, 1)))
    D = jnp.ones((di,), jnp.float32)
    got = jax.jit(ops.ssm_scan)(u, dt, Bs, Cs, A_log, D).astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(ref.ssm_scan)(u, dt, Bs, Cs, A_log, D).astype(
            jnp.float32)
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


def phase_c(*, model: ModelSpec = ModelSpec(
        arch="falcon-mamba-7b", reduced=False, overrides={"num_layers": 2}),
            batch: int = 2, seq_len: int = 1024, steps: int = 3,
            stages: int = 2, eval_rows: int = 2,
            parity_shape=(1, 2048, 8192, 16)) -> dict:
    """Train through the mamba family; returns what the checks read."""
    ops.reset_calls()
    profile.STAGE_KERNELS.clear()
    res = _train_lm(_lm_spec(model, batch=batch, seq_len=seq_len,
                             steps=steps, stages=stages,
                             eval_rows=eval_rows), profile.StageProfiler())
    _check_lm(f"C {model.arch}", res, falls=False)
    res["calls"] = ops.CALLS["ssm_scan"]
    # the first stage kernel's executable, as compiled for the run
    lowered, _ = next(iter(profile.STAGE_KERNELS.values()))
    res["hlo"] = lowered.compile().as_text()
    res["parity"] = {}
    for dtype in (jnp.float32, jnp.bfloat16):
        name = jnp.dtype(dtype).name
        err = ssm_parity(parity_shape, dtype)
        res["parity"][name] = err
        observe(f"C: ssm_scan {name} {parity_shape} vs ref (highest): "
                f"max|diff|/max|ref| = {err:.3e} (tolerance {SSM_TOL[name]})")
        if not err <= SSM_TOL[name]:
            raise AssertionError(f"C: ssm_scan {name} off by {err}")
    if res["calls"] < 1:
        raise AssertionError("C: ssm_scan was never dispatched")
    return res


def check_c_on_chip(res: dict) -> None:
    n = res["hlo"].count('custom_call_target="tpu_custom_call"')
    observe(f"C: {n} tpu_custom_call(s) in the compiled stage HLO, "
            f"{res['calls']} ssm_scan dispatch(es)")
    if not n:
        raise AssertionError("C: no tpu_custom_call in the stage HLO")


def four_chips(*, scale: float = 1.0, shard_size: int = 128) -> dict:
    """bench_dist's distributed convex BET, one simulated host per chip."""
    hosts = 4
    data = DataSpec(dataset="webspam_like", scale=scale, lam=1e-3)
    base = dict(policy=PolicySpec("fixed_steps", {"inner_steps": 5,
                                                  "final_steps": 25}),
                optimizer=OptimizerSpec("newton_cg",
                                        {"hessian_fraction": 1.0}))
    problem, _, _ = convex_problem(data)
    n0 = max(128, min(problem.d, problem.n // 8))
    single = build(RunSpec(data=data, **base, schedule=ScheduleSpec(n0=n0)))
    t0 = time.perf_counter()
    tr_1 = single.run()
    wall_1 = time.perf_counter() - t0
    dist = build(RunSpec(
        data=data.replace(plane="plane", shard_size=shard_size), **base,
        schedule=ScheduleSpec(n0=n0), topology=TopologySpec(hosts=hosts)))
    t0 = time.perf_counter()
    tr_4 = dist.run()
    wall_4 = time.perf_counter() - t0
    lanes = {}
    for buf in (sw.buffer for sw in dist.dataset.stacked):
        for shard in buf.addressable_shards:
            lane = shard.index[0]
            lanes.setdefault(shard.device.id, set()).add(
                (lane.start, lane.stop))
    rel = max(
        float(np.max(np.abs(a - b) / np.maximum(np.abs(a), 1e-12)))
        for a, b in ((np.asarray(tr_1.column(c)), np.asarray(tr_4.column(c)))
                     for c in ("f_window", "f_full")))
    observe(f"4 chips: n={problem.n} hosts={hosts} lanes by device "
            f"{ {d: sorted(v) for d, v in lanes.items()} } "
            f"wall_s single={wall_1:.3f} 4-host={wall_4:.3f} "
            f"host_transfers={tr_4.meta['host_transfers']} "
            f"stages={tr_4.meta['stages']} max_rel_dev={rel:.3e} "
            f"(bound {REL_TOL_4})")
    if len(lanes) != hosts or any(len(v) != 1 for v in lanes.values()) or \
            len({lane for v in lanes.values() for lane in v}) != hosts:
        raise AssertionError(f"4 chips: lanes not one per chip: {lanes}")
    if not rel <= REL_TOL_4:
        raise AssertionError(f"4 chips: trajectory off by rel {rel}")
    return {"lanes": lanes, "rel": rel}


# ----------------------------------------------------------------- main
def _run(name: str, fn, clock: CompileClock) -> bool:
    t0, c0 = time.perf_counter(), clock.seconds
    try:
        fn()
    except Exception:
        traceback.print_exc()
        print(f"phase {name} FAILED after {time.perf_counter() - t0:.1f}s",
              flush=True)
        return False
    finally:
        gc.collect()
    print(f"phase {name} ok in {time.perf_counter() - t0:.1f}s, of which "
          f"compile_s={clock.seconds - c0:.1f} peak_bytes_in_use(process)="
          f"{peak_bytes()}", flush=True)
    return True


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the distributed convex BET on 4 chips")
    args = ap.parse_args()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: found {dev.platform} ({dev.device_kind}), not a "
              f"TPU; nothing was run", file=sys.stderr)
        return 2
    if args.four_chips and len(devices) != 4:
        print(f"chip_smoke: --four-chips needs 4 chips, found "
              f"{len(devices)}", file=sys.stderr)
        return 2
    observe(f"device {dev.platform} {dev.device_kind} x{len(devices)}, "
            f"compile cache {use_compile_cache()}")
    clock = CompileClock()
    if args.four_chips:
        phases = [("4-chips", four_chips)]
    else:
        # A's 1 GiB dataset stays memoized by repro.api for the process,
        # so it runs last, after B's and C's models have left HBM
        phases = [("B", phase_b),
                  ("C", lambda: check_c_on_chip(phase_c())),
                  ("A", phase_a)]
    ok = [_run(name, fn, clock) for name, fn in phases]
    if not all(ok):
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
