"""Unified Batch-Expansion Training engine.

The paper's central claim is that BET "can be easily paired with most batch
optimizers" and that the *when-to-expand* decision is orthogonal to the
*how-to-step* loop.  This module factors the repo accordingly:

  * ``ExpansionPolicy`` — a small protocol (``stage_begin`` /
    ``should_expand`` / ``stage_end`` plus a ``plan_steps`` sizing hook)
    that decides when the window grows.  Shipped policies:

      - ``FixedSteps``        Algorithm 1/3: κ̂ inner iterations per stage,
      - ``TwoTrack``          Algorithm 2: the parameter-free condition (3),
      - ``NeverExpand``       the Batch baseline (one full-window stage),
      - ``GradientVariance``  beyond-paper: the Byrd et al. (2012) /
                              AdaDamp-style norm test applied to BET's
                              resampling-free expanding window.

  * ``BetEngine.run(dataset, optimizer, objective, policy, ...)`` — the one
    driver behind ``run_batch`` / ``run_bet_fixed`` / ``run_two_track``
    (core/bet.py), the DSM helpers (core/dsm.py) and the distributed LM
    path (launch/train.py).

Stages execute **device-side**: inner iterations run in chunks through
``BatchOptimizer.run`` (``lax.scan``) with donated carries; the Two-Track
race runs as a single ``lax.while_loop`` with its condition-(3) trigger
evaluated on device.  Per-step measurements — f̂_t(w), f̂(w) and the
time-model inputs — accumulate in device arrays and are transferred to the
host **once per stage** (``trace.meta["host_transfers"]`` counts the
``device_get`` calls), eliminating the legacy drivers' 2–3 blocking host
syncs per inner step.  Jitted stage kernels are cached per
(optimizer, objective, kernel-flavor) in a module-level table, so repeated
stages — and repeated runs — with the same window shape never re-trace; the
legacy loops re-jitted a fresh lambda every stage.

The host-side originals are preserved verbatim in core/legacy.py for A/B
parity tests and benchmarks/bench_engine.py.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from ..obs.events import obs_span
from ..optim.api import BatchOptimizer, Objective
from .timemodel import SimulatedClock
from .trace import Trace


# ------------------------------------------------------------------ schedule
@dataclasses.dataclass(frozen=True)
class BETSchedule:
    """Stage schedule: n_{t+1} = growth * n_t (paper: growth=2, §3.5 notes the
    factor is not critical), ε_{t+1} = ε_t / growth."""
    n0: int = 200
    growth: float = 2.0

    def __post_init__(self):
        if self.n0 < 1:
            raise ValueError(f"BETSchedule.n0 must be >= 1, got {self.n0}")
        if not self.growth > 1.0:
            raise ValueError(
                f"BETSchedule.growth must be > 1, got {self.growth}: the "
                "window n_t = n0 * growth^t would never reach the dataset")

    def windows(self, N: int) -> list[int]:
        ns, n = [], self.n0
        while n < N:
            ns.append(n)
            n = min(N, int(math.ceil(n * self.growth)))
        ns.append(N)
        return ns


# ------------------------------------------------------------- resume/hooks
@dataclasses.dataclass(frozen=True)
class ResumeState:
    """Where a checkpointed run left off.  ``next_stage`` is the first stage
    index to execute; the counters seed the engine context so step numbering,
    stage counts and transfer accounting continue exactly where the
    uninterrupted run would be (Thm 4.1 bookkeeping survives the restart).
    The caller restores params/opt-state/clock/meters separately
    (elastic/checkpoint.py bundles all of it)."""
    next_stage: int
    step_count: int = 0
    stages: int = 0
    transfers: int = 0


@dataclasses.dataclass
class StageEnd:
    """What the once-per-stage boundary hook sees: everything a stage
    checkpoint must capture (params, optimizer state, cursor, clock,
    dataset meters) plus the live trace for event annotations."""
    info: "StageInfo"
    params: Any
    opt_state: Any
    clock: SimulatedClock
    dataset: Any
    trace: Trace
    step_count: int
    stages: int
    transfers: int


# ------------------------------------------------------------------ protocol
@dataclasses.dataclass
class StageInfo:
    """What a policy sees about the current stage.  ``n_next`` is the
    window the schedule will expand to afterwards (None on the last stage)
    — the streaming data plane prefetches its shards during this stage."""
    stage: int
    n_t: int
    n_prev: int
    is_final: bool
    N: int
    n_next: int | None = None


class StageRecords:
    """Host-side accumulator for one stage's transferred measurements."""

    def __init__(self):
        self._f_window: list[np.ndarray] = []
        self._f_full: list[np.ndarray] = []
        self._params: list[Any] = []          # per-chunk stacked param pytrees
        self.f_fast_on_t: np.ndarray | None = None   # two-track only
        self.triggered: bool = False                  # two-track condition (3)
        self.var: float = 0.0                         # gradient-variance stats
        self.g2: float = 0.0

    def add_chunk(self, f_window, f_full=None, params=None):
        self._f_window.append(np.asarray(f_window))
        if f_full is not None:
            self._f_full.append(np.asarray(f_full))
        if params is not None:
            self._params.append(params)

    @property
    def steps(self) -> int:
        return sum(len(c) for c in self._f_window)

    def chunk_lengths(self) -> list[int]:
        return [len(c) for c in self._f_window]

    def f_window(self) -> np.ndarray:
        return np.concatenate(self._f_window) if self._f_window else np.empty(0)

    def f_full(self) -> np.ndarray:
        if not self._f_full:
            return self.f_window()          # policy opted out of full evals
        return np.concatenate(self._f_full)

    def param_at(self, i: int):
        """The (host) parameter pytree after inner step ``i`` of this stage."""
        for chunk in self._params:
            k = len(jax.tree_util.tree_leaves(chunk)[0])
            if i < k:
                return jax.tree_util.tree_map(lambda b: b[i], chunk)
            i -= k
        raise IndexError(i)


class ExpansionPolicy:
    """When-to-expand protocol.  The engine owns stepping, clock accounting
    and tracing; the policy only answers scheduling questions:

      stage_begin(info)            — a new window n_t is about to run
      plan_steps(info, done)       — how many inner steps to scan before the
                                     next should_expand consultation
      should_expand(info, records) — stage over?  (records hold everything
                                     transferred so far this stage)
      stage_end(info, records)     — the stage finished

    ``kind == "two_track"`` routes stages through the while_loop race kernel
    (the trigger then fires on device and ``should_expand`` just confirms
    it); every other policy runs scan chunks.
    """
    name = "policy"
    kind = "scan"               # "scan" | "two_track"
    eval_full = True            # evaluate f̂(w) per step (False: f_full := f_window)
    wants_variance = False      # compute per-example gradient-variance stats
    record_every = 1
    probe = 0

    def windows(self, schedule: BETSchedule, N: int) -> list[int]:
        return schedule.windows(N)

    def stage_begin(self, info: StageInfo) -> None:
        pass

    def plan_steps(self, info: StageInfo, done_steps: int) -> int:
        raise NotImplementedError

    def should_expand(self, info: StageInfo, records: StageRecords) -> bool:
        return True

    def stage_end(self, info: StageInfo, records: StageRecords) -> None:
        pass


@dataclasses.dataclass
class FixedSteps(ExpansionPolicy):
    """Algorithm 1/3: a fixed κ̂ inner iterations per stage, ``final_steps``
    on the full window (Theorem 4.1 sets κ̂ from the inner rate; §4.2: 2–4)."""
    inner_steps: int = 8
    final_steps: int = 40
    name = "bet"

    def plan_steps(self, info, done_steps):
        return self.final_steps if info.is_final else self.inner_steps


@dataclasses.dataclass
class NeverExpand(ExpansionPolicy):
    """The Batch baseline: a single stage on the full dataset."""
    steps: int = 30
    record_every: int = 1
    eval_full: bool = False     # window == full data; legacy records f_full := f
    name = "batch"

    def windows(self, schedule, N):
        return [N]

    def plan_steps(self, info, done_steps):
        return self.steps


@dataclasses.dataclass
class TwoTrack(ExpansionPolicy):
    """Algorithm 2: primary (slow) track on n_t races a secondary (fast)
    track on n_{t-1} from the same stage-start point; expansion triggers on
    condition (3): f̂_t(w_{t,⌊s/2⌋}) < f̂_t(w'_{t-1,s}).  Parameter-free.

    ``condition="aux"`` compares the slow track's own per-step objective
    (the convex drivers); ``condition="eval"`` re-evaluates both tracks on a
    probe of the stage window (the stochastic LM path)."""
    final_steps: int = 40
    max_stage_iters: int = 500          # safety bound; condition (3) always fires
    charge_condition_eval: bool = True
    condition: str = "aux"              # "aux" | "eval"
    final_eval_full: bool = False       # legacy final phase records f_full := f
    name = "bet_two_track"
    kind = "two_track"

    def plan_steps(self, info, done_steps):        # final phase only
        return self.final_steps

    def should_expand(self, info, records):
        if records.f_fast_on_t is not None:   # racing stage: device-side trigger
            return records.triggered or records.steps >= self.max_stage_iters
        return records.steps >= self.final_steps    # final phase budget spent


@dataclasses.dataclass
class GradientVariance(ExpansionPolicy):
    """Beyond-paper adaptive trigger: the gradient-variance "norm test" of
    DSM (Byrd, Chin, Nocedal, Wu 2012) / AdaDamp (Alfarra et al.), applied
    to BET's *resampling-free* expanding window.  After each chunk the
    engine measures, on a ``probe``-point prefix of the resident window,

        v = ‖Var_i ∇ℓ_i(w)‖₁ / k     vs     g² = ‖∇f̂_t(w)‖² ;

    once noise dominates signal (v > θ² g²) the window's gradient has no
    more to teach and the stage ends.  Unlike DSM this touches no new data
    until the expansion itself, so Thm 4.1's access bound still applies.
    Expansion is monotone by construction (windows are nested prefixes).
    Requires ``data = (X, y)`` with per-example rows (the convex path)."""
    theta: float = 0.5
    probe: int = 256
    chunk: int = 4
    min_stage_steps: int = 2
    max_stage_iters: int = 64
    final_steps: int = 40
    name = "bet_gradvar"
    wants_variance = True

    def plan_steps(self, info, done_steps):
        return self.final_steps if info.is_final else self.chunk

    def should_expand(self, info, records):
        if info.is_final or records.steps >= self.max_stage_iters:
            return True
        if records.steps < self.min_stage_steps:
            return False
        return records.var > (self.theta ** 2) * max(records.g2, 1e-30)


class ComposedPolicy(ExpansionPolicy):
    """Policy composition (ROADMAP follow-up): one primary policy owns the
    stage loop shape (scan chunks or the two-track race) and the expansion
    proposal; ``vetoes`` must all concur before an expansion is allowed
    (logical AND — e.g. TwoTrack proposing, a GradientVariance veto holding
    the stage while the window's gradient still has signal); ``any_of`` may
    force an expansion the primary has not proposed yet (logical OR).

    The combinator only answers scheduling questions — stepping, clock
    accounting and tracing stay with the engine — so any scan-kind policy
    composes freely; a two-track policy may only sit in the ``primary``
    slot (its condition-(3) trigger runs inside the race kernel, and the
    engine re-races the stage when a veto holds it open).  Unknown
    attributes delegate to the primary, so engine lookups like
    ``max_stage_iters`` / ``charge_condition_eval`` see the primary's."""

    def __init__(self, primary: ExpansionPolicy, vetoes=(), any_of=()):
        self.primary = primary
        self.vetoes = tuple(vetoes)
        self.any_of = tuple(any_of)
        members = (primary,) + self.vetoes + self.any_of
        for p in self.vetoes + self.any_of:
            if p.kind != "scan":
                raise ValueError(
                    f"policy {p.name!r} is {p.kind!r}-kind: only the "
                    f"primary slot of a ComposedPolicy may be two_track "
                    f"(the race kernel cannot run as a veto)")
        self.name = "composed(" + "+".join(p.name for p in members) + ")"
        self.kind = primary.kind
        self.eval_full = primary.eval_full
        self.record_every = primary.record_every
        self.wants_variance = any(p.wants_variance for p in members)
        self.probe = max((int(p.probe) for p in members), default=0)

    def __getattr__(self, item):
        if item == "primary":           # guard pre-__init__ lookups
            raise AttributeError(item)
        return getattr(self.primary, item)

    def windows(self, schedule: BETSchedule, N: int) -> list[int]:
        return self.primary.windows(schedule, N)

    def stage_begin(self, info: StageInfo) -> None:
        for p in (self.primary,) + self.vetoes + self.any_of:
            p.stage_begin(info)

    def plan_steps(self, info: StageInfo, done_steps: int) -> int:
        return self.primary.plan_steps(info, done_steps)

    def should_expand(self, info: StageInfo, records: StageRecords) -> bool:
        if any(p.should_expand(info, records) for p in self.any_of):
            return True
        if not self.primary.should_expand(info, records):
            return False
        return all(p.should_expand(info, records) for p in self.vetoes)

    def stage_end(self, info: StageInfo, records: StageRecords) -> None:
        for p in (self.primary,) + self.vetoes + self.any_of:
            p.stage_end(info, records)


# ------------------------------------------------------------ stage kernels
_KERNEL_CACHE: dict[tuple, Callable] = {}


def variance_stats(objective: Objective, w, data, k: int):
    """(‖Var_i ∇ℓ_i‖₁ / k, ‖ḡ‖²) over the first ``k`` rows of (X, y) —
    per-example gradients via vmap; the DSM / GradientVariance test."""
    X, y = data
    Xp, yp = X[:k], y[:k]

    def per_example(xi, yi):
        return jax.grad(objective)(w, (xi[None], yi[None]))

    gs = jax.vmap(per_example)(Xp, yp)
    gbar = jax.tree_util.tree_map(lambda g: jnp.mean(g, axis=0), gs)
    var = jax.tree_util.tree_map(
        lambda g, m: jnp.mean((g - m) ** 2, axis=0), gs, gbar)
    v = jax.tree_util.tree_reduce(
        jnp.add, jax.tree_util.tree_map(jnp.sum, var), jnp.float32(0.0)) / k
    g2 = jax.tree_util.tree_reduce(
        jnp.add, jax.tree_util.tree_map(lambda m: jnp.sum(m ** 2), gbar),
        jnp.float32(0.0))
    return v, g2


def cached_step(optimizer: BatchOptimizer, objective: Objective) -> Callable:
    """A jitted single step, cached per (optimizer, objective) so repeated
    callers (e.g. the DSM loop) re-trace only on new data shapes."""
    key = ("step", optimizer, objective)
    if key not in _KERNEL_CACHE:
        _KERNEL_CACHE[key] = jax.jit(
            lambda p, s, d: optimizer.step(p, s, objective, d))
    return _KERNEL_CACHE[key]


def cached_eval(objective: Objective) -> Callable:
    """A jitted ``objective(w, data)``, cached per objective."""
    key = ("eval", objective)
    if key not in _KERNEL_CACHE:
        _KERNEL_CACHE[key] = jax.jit(objective)
    return _KERNEL_CACHE[key]


def cached_variance(objective: Objective) -> Callable:
    """Jitted ``variance_stats`` with a static probe size."""
    key = ("var", objective)
    if key not in _KERNEL_CACHE:
        _KERNEL_CACHE[key] = jax.jit(
            lambda w, d, k: variance_stats(objective, w, d, k),
            static_argnames=("k",))
    return _KERNEL_CACHE[key]


def _scan_kernel(optimizer, objective, *, eval_full: bool,
                 collect_params: bool, variance: bool) -> Callable:
    """One stage chunk: ``num_steps`` inner iterations via BatchOptimizer.run
    (lax.scan), with per-step measurements accumulated on device."""
    key = ("scan", optimizer, objective, eval_full, collect_params, variance)
    if key in _KERNEL_CACHE:
        return _KERNEL_CACHE[key]

    def kernel(params, state, window, full_data, num_steps, probe_k):
        def collect(p, aux):
            out = {"f": aux["f"]}
            if eval_full:
                with jax.named_scope("bet.probe"):
                    out["f_full"] = objective(p, full_data)
            if collect_params:
                out["w"] = p
            return out

        params, state, outs = optimizer.run(params, state, objective, window,
                                            num_steps, collect=collect)
        res = {"params": params, "state": state, **outs}
        if variance:
            res["var"], res["g2"] = variance_stats(
                objective, params, window, probe_k)
        return res

    jitted = jax.jit(kernel, static_argnames=("num_steps", "probe_k"),
                     donate_argnums=(0, 1))
    _KERNEL_CACHE[key] = jitted
    return jitted


def _two_track_kernel(optimizer, objective, *, condition_eval: bool,
                      collect_params: bool) -> Callable:
    """One full Two-Track racing stage as a device-side lax.while_loop:
    both tracks step, condition (3) is tested on device against a history
    buffer, and the stage's per-step measurements come back in one pull."""
    key = ("two_track", optimizer, objective, condition_eval, collect_params)
    if key in _KERNEL_CACHE:
        return _KERNEL_CACHE[key]

    def kernel(w, st_slow, st_fast, win_t, win_prev, full_data, max_iters):
        M = max_iters
        zeros = jnp.zeros((M,), jnp.float32)
        W0 = (jax.tree_util.tree_map(
            lambda x: jnp.zeros((M,) + x.shape, x.dtype), w)
            if collect_params else None)

        def cond(c):
            return jnp.logical_and(~c["done"], c["s"] < M)

        def body(c):
            w_s, st_s, aux = optimizer.step(c["w_slow"], c["st_slow"],
                                            objective, win_t)
            w_f, st_f, _ = optimizer.step(c["w_fast"], c["st_fast"],
                                          objective, win_prev)
            f_slow = objective(w_s, win_t) if condition_eval else aux["f"]
            f_fast = objective(w_f, win_t)
            with jax.named_scope("bet.probe"):
                f_full = objective(w_s, full_data)
            s = c["s"]
            hs = c["hist_slow"].at[s].set(f_slow)
            hf = c["hist_fast"].at[s].set(f_fast)
            hfull = c["hist_full"].at[s].set(f_full)
            nxt = dict(w_slow=w_s, st_slow=st_s, w_fast=w_f, st_fast=st_f,
                       s=s + 1, hist_slow=hs, hist_fast=hf, hist_full=hfull)
            if collect_params:
                nxt["W"] = jax.tree_util.tree_map(
                    lambda b, v: b.at[s].set(v), c["W"], w_s)
            # condition (3): slow at ⌊s/2⌋ already beats fast at s
            s1 = s + 1
            k = jnp.maximum(0, s1 // 2 - 1)
            nxt["done"] = jnp.logical_and(s1 >= 2, hs[k] < f_fast)
            return nxt

        init = dict(w_slow=w, st_slow=st_slow, w_fast=w, st_fast=st_fast,
                    s=jnp.int32(0), done=jnp.bool_(False),
                    hist_slow=zeros, hist_fast=zeros, hist_full=zeros)
        if collect_params:
            init["W"] = W0
        final = jax.lax.while_loop(cond, body, init)
        out = {"params": final["w_slow"], "state": final["st_slow"],
               "s": final["s"], "triggered": final["done"],
               "f_slow": final["hist_slow"], "f_fast": final["hist_fast"],
               "f_full": final["hist_full"]}
        if collect_params:
            out["W"] = final["W"]
        return out

    jitted = jax.jit(kernel, static_argnames=("max_iters",),
                     donate_argnums=(0, 1, 2))
    _KERNEL_CACHE[key] = jitted
    return jitted


# ---------------------------------------------------------------- the engine
@dataclasses.dataclass
class BetEngine:
    """The single BET driver.  Policies decide *when* to expand; the engine
    owns stepping (device-side), clock accounting (host replay of the §4.2
    charges after each once-per-stage transfer) and tracing.

    ``step_cost`` maps the stage window n_t to the points one inner step
    charges the clock: the convex drivers pay the whole window (default);
    the LM path pays one mini-batch.  ``wait_on_expand`` blocks the clock on
    window residency at stage entry (the ExpandingWindow.grow contract);
    ``carry_state`` keeps optimizer state across Two-Track stages instead of
    re-initializing (the LM path's persistent Adam moments)."""
    schedule: BETSchedule = dataclasses.field(default_factory=BETSchedule)
    step_cost: Callable[[int], int] | None = None
    wait_on_expand: bool = False
    carry_state: bool = False
    max_engine_steps: int = 100_000     # runaway-policy backstop
    # once-per-stage boundary callback (StageEnd) — stage checkpointing
    # plugs in here without subclassing; fault injection subclasses
    # _stage_boundary instead (elastic/runtime.py)
    stage_callback: Callable | None = None
    # observability (repro.obs): a wired EventRecorder makes the engine emit
    # structured stage spans/instants/counters; a StageProfiler additionally
    # keeps each stage kernel's executable, so its compiled ops can be named
    # by scope (repro.obs.profile).  Both off by default — the stage
    # trajectory is bit-identical either way.
    recorder: Any | None = None
    profiler: Any | None = None

    def run(self, dataset, optimizer: BatchOptimizer, objective: Objective,
            policy: ExpansionPolicy, *, w0=None, clock: SimulatedClock | None = None,
            eval_data=None, probe: Callable | None = None,
            trace_name: str | None = None, meta: dict | None = None,
            progress: Callable | None = None, opt_state0=None,
            resume: ResumeState | None = None) -> Trace:
        clock = clock or SimulatedClock()
        N = dataset.n
        # NB: with a StreamingDataset, omitting eval_data forces the whole
        # corpus resident here (f̂ needs all N points) and defeats staged
        # loading — pass an eval set/probe to keep the plane streaming.
        full_data = eval_data if eval_data is not None else dataset.window(N)
        w = w0 if w0 is not None else jnp.zeros((dataset.d,), jnp.float32)
        # private copy: stage kernels donate their carries, which must never
        # invalidate a caller-owned w0 buffer
        w = jax.tree_util.tree_map(jnp.array, w)
        state = optimizer.init(w) if opt_state0 is None else \
            jax.tree_util.tree_map(jnp.array, opt_state0)
        trace = Trace(trace_name or policy.name,
                      meta={"engine": "BetEngine", "policy": policy.name,
                            "optimizer": optimizer.name, **(meta or {})})
        cost = self.step_cost or (lambda n: n)
        run_ctx = {"trace": trace, "clock": clock, "cost": cost,
                   "probe": probe, "progress": progress, "dataset": dataset,
                   "step_count": 0, "transfers": 0, "stages": 0}
        first_stage = 0
        if resume is not None:
            run_ctx.update(step_count=resume.step_count,
                           transfers=resume.transfers, stages=resume.stages)
            first_stage = resume.next_stage
            trace.meta["resumed_from_stage"] = first_stage - 1

        if policy.kind == "two_track":
            w, state = self._run_two_track(
                run_ctx, dataset, optimizer, objective, policy,
                w, state, full_data, first_stage=first_stage)
        else:
            for info in self.stage_infos(policy, N):
                if info.stage < first_stage:
                    continue            # completed before the checkpoint
                state = optimizer.reset_memory(state)  # f̂_t changed
                w, state = self._run_scan_stage(
                    run_ctx, dataset, optimizer, objective, policy, info,
                    w, state, full_data)
        trace.params = w
        trace.meta["host_transfers"] = run_ctx["transfers"]
        trace.meta["stages"] = run_ctx["stages"]
        return trace

    # ------------------------------------------------------------ online runs
    def run_online(self, dataset, optimizer: BatchOptimizer,
                   objective: Objective, policy: ExpansionPolicy, *,
                   source=None, w0=None, clock: SimulatedClock | None = None,
                   eval_data=None, probe: Callable | None = None,
                   trace_name: str | None = None, meta: dict | None = None,
                   progress: Callable | None = None, opt_state0=None,
                   max_stages: int = 10_000) -> Trace:
        """``run`` over a corpus still *arriving* (serve-while-you-train).

        ``run`` precomputes the stage plan from ``dataset.n`` once; here the
        corpus size is discovered as the serving path logs requests, so the
        stage plan is built one stage at a time: each stage targets
        ``n_next = ceil(growth * n_t)`` and the policy (normally
        serve/policy.TrafficDriven) *holds the stage open* — more inner
        steps on the current window — until enough new examples have been
        sealed to honor that target, or the ``source`` store closes.  Once
        the source is closed and the window covers everything sealed, one
        final full-window stage runs and the loop ends — from there the
        trace is indistinguishable from an offline ``run`` whose schedule
        happened to emit the same windows (expansion stayed append-only).

        ``eval_data`` is required: with the corpus still arriving there is
        no full-window f̂ to fall back to.  Two-track policies are rejected
        — the race kernel needs the *next* window resident up front, which
        is exactly what an online corpus cannot promise.
        """
        if eval_data is None:
            raise ValueError(
                "run_online requires eval_data: the full corpus is not "
                "available for f̂ while data is still arriving")
        if policy.kind == "two_track":
            raise ValueError(
                f"policy {policy.name!r} is two_track-kind: the race needs "
                f"next-window residency up front; run_online supports only "
                f"scan policies")
        if dataset.n < 1:
            raise ValueError(
                "run_online needs at least one sealed example before "
                "training starts (seed the source first)")
        clock = clock or SimulatedClock()
        w = w0 if w0 is not None else jnp.zeros((dataset.d,), jnp.float32)
        w = jax.tree_util.tree_map(jnp.array, w)
        state = optimizer.init(w) if opt_state0 is None else \
            jax.tree_util.tree_map(jnp.array, opt_state0)
        trace = Trace(trace_name or policy.name,
                      meta={"engine": "BetEngine.online",
                            "policy": policy.name,
                            "optimizer": optimizer.name, **(meta or {})})
        cost = self.step_cost or (lambda n: n)
        run_ctx = {"trace": trace, "clock": clock, "cost": cost,
                   "probe": probe, "progress": progress, "dataset": dataset,
                   "step_count": 0, "transfers": 0, "stages": 0}
        growth = self.schedule.growth
        stage = 0
        n_t = min(self.schedule.n0, dataset.n)
        n_prev = n_t
        while True:
            closed = bool(getattr(source, "closed", True))
            is_final = closed and n_t >= dataset.n
            n_next = None if is_final else \
                max(n_t + 1, int(math.ceil(n_t * growth)))
            info = StageInfo(stage=stage, n_t=n_t, n_prev=n_prev,
                             is_final=is_final, N=dataset.n, n_next=n_next)
            state = optimizer.reset_memory(state)
            w, state = self._run_scan_stage(
                run_ctx, dataset, optimizer, objective, policy, info,
                w, state, eval_data)
            if is_final:
                break
            # the stage was held open until the target (or close) landed;
            # clip to what is actually sealed now
            n_prev, n_t = n_t, min(dataset.n, n_next)
            stage += 1
            if stage > max_stages:
                raise RuntimeError(
                    f"run_online exceeded {max_stages} stages without the "
                    f"source closing")
        trace.params = w
        trace.meta["host_transfers"] = run_ctx["transfers"]
        trace.meta["stages"] = run_ctx["stages"]
        trace.meta["final_n"] = dataset.n
        return trace

    # ---------------------------------------------------------- stage windows
    def stage_infos(self, policy: ExpansionPolicy, N: int) -> list[StageInfo]:
        """The stages a run of ``policy`` over ``N`` examples executes, in
        order — the single definition behind the run loops and the
        session's ``stage_plan()`` (dry-run printing).  Two-track runs race
        stages 1..T over consecutive window pairs, then a final full-window
        phase; scan policies run one stage per window."""
        windows = policy.windows(self.schedule, N)
        if policy.kind == "two_track":
            infos = [StageInfo(stage=stage, n_t=windows[stage],
                               n_prev=windows[stage - 1],
                               is_final=windows[stage] >= N, N=N,
                               n_next=windows[stage + 1]
                               if stage + 1 < len(windows) else None)
                     for stage in range(1, len(windows))]
            infos.append(StageInfo(stage=len(windows), n_t=N, n_prev=N,
                                   is_final=True, N=N))
            return infos
        return [StageInfo(stage=stage, n_t=n_t,
                          n_prev=windows[stage - 1] if stage else n_t,
                          is_final=n_t >= N, N=N,
                          n_next=windows[stage + 1]
                          if stage + 1 < len(windows) else None)
                for stage, n_t in enumerate(windows)]

    @staticmethod
    def _acquire_window(dataset, n_t: int, n_next: int | None):
        """Stage setup against the data plane: a ``StreamingDataset`` makes
        the stage window device-resident and starts prefetching the *next*
        expansion's shards (so their loads overlap this stage's compute);
        plain datasets fall back to the host-slice window protocol."""
        begin = getattr(dataset, "begin_stage", None)
        if begin is not None:
            return begin(n_t, n_next)
        return dataset.window(n_t)

    @staticmethod
    def _segment_plan(dataset, info: StageInfo, k: int):
        """Chunk plan against the data plane: a tiered corpus whose stage
        window exceeds the HBM budget splits the chunk's ``k`` steps across
        its hot-window sweep (``[(steps, examples_per_step), ...]`` — the
        engine calls ``advance_window`` between entries); every other plane
        runs the chunk in one piece at full window cost (``None`` ->
        ``info.n_t``)."""
        plan = getattr(dataset, "segment_steps", None)
        if plan is None:
            return [(k, None)]
        return plan(info.n_t, k)

    # ------------------------------------------------------------ scan stages
    def _run_scan_stage(self, ctx, dataset, optimizer, objective, policy,
                        info: StageInfo, w, state, full_data, *,
                        eval_full=None, extra_base=None):
        clock, cost = ctx["clock"], ctx["cost"]
        obs = self.recorder
        eval_full = policy.eval_full if eval_full is None else eval_full
        collect_params = ctx["probe"] is not None
        if obs is not None:
            obs.set_context(stage=info.stage)
            obs.instant("stage.begin", window=info.n_t, n_next=info.n_next,
                        final=info.is_final)
        with obs_span(obs, "stage.acquire", window=info.n_t):
            win = self._acquire_window(dataset, info.n_t, info.n_next)
        if self.wait_on_expand:
            clock.wait_for(info.n_t)
        kernel = _scan_kernel(optimizer, objective, eval_full=eval_full,
                              collect_params=collect_params,
                              variance=policy.wants_variance)
        probe_k = min(int(policy.probe), info.n_t) if policy.wants_variance else 0
        policy.stage_begin(info)
        rec = StageRecords()
        chunk_costs: list = []
        while True:
            k = int(policy.plan_steps(info, rec.steps))
            plan = self._segment_plan(dataset, info, k)
            for seg_j, (kj, seg_n) in enumerate(plan):
                if seg_j:
                    # rotation: land the next pre-staged sweep segment
                    with obs_span(obs, "stage.acquire", window=info.n_t,
                                  segment=seg_n):
                        win = dataset.advance_window()
                pk = probe_k if seg_n is None else min(probe_k, seg_n)
                if self.profiler is not None and rec.steps == 0:
                    self.profiler.observe(info, kernel,
                                          (w, state, win, full_data),
                                          {"num_steps": kj, "probe_k": pk})
                with obs_span(obs, "stage.compute", steps=kj,
                              window=info.n_t):
                    out = kernel(w, state, win, full_data, num_steps=kj,
                                 probe_k=pk)
                    w, state = out["params"], out["state"]
                    pulled = jax.device_get(
                        {n: v for n, v in out.items()
                         if n not in ("params", "state")})
                ctx["transfers"] += 1
                if obs is not None:
                    obs.instant("engine.transfer", transfers=ctx["transfers"])
                rec.add_chunk(pulled["f"], pulled.get("f_full"),
                              pulled.get("w"))
                chunk_costs.append(seg_n)
            if policy.wants_variance:
                rec.var, rec.g2 = float(pulled["var"]), float(pulled["g2"])
            expand = policy.should_expand(info, rec)
            if obs is not None:
                fs = pulled["f"]
                obs.instant("expand.decision", expand=bool(expand),
                            window=info.n_t, steps=rec.steps,
                            var=rec.var, g2=rec.g2,
                            triggered=bool(rec.triggered),
                            f_last=float(fs[-1]) if len(fs) else None)
            if expand:
                break
            if rec.steps > self.max_engine_steps:
                raise RuntimeError(
                    f"policy {policy.name} never expanded after {rec.steps} steps")
        with obs_span(obs, "stage.flush", window=info.n_t):
            self._flush_stage(ctx, policy, info, rec, extra_base=extra_base,
                              eval_charge=probe_k, chunk_costs=chunk_costs)
        policy.stage_end(info, rec)
        self._stage_boundary(ctx, info, w, state)
        if obs is not None:
            obs.instant("stage.end", window=info.n_t)
            obs.clear_context("stage")
        return w, state

    def _stage_boundary(self, ctx, info: StageInfo, w, state) -> None:
        """Once-per-stage boundary: the stage's records are flushed, the
        trace is current, and (w, state) are the exact carries the next
        stage starts from — the one point where a checkpoint captures a
        resumable run and where elastic events (host loss/join, straggler
        rebalancing) are injected between stages."""
        if self.stage_callback is not None:
            self.stage_callback(StageEnd(
                info=info, params=w, opt_state=state, clock=ctx["clock"],
                dataset=ctx["dataset"], trace=ctx["trace"],
                step_count=ctx["step_count"], stages=ctx["stages"],
                transfers=ctx["transfers"]))

    def _collect_host_records(self, ctx, info: StageInfo) -> None:
        """Once-per-stage flush hook, called right before the trace lands.
        The multi-host runtime (dist/runtime.DistributedBetEngine) overrides
        this to all-gather per-host stage records through its communicator;
        the single-host engine records nothing extra."""

    def _flush_stage(self, ctx, policy, info: StageInfo, rec: StageRecords,
                     *, extra_base=None, eval_charge: int = 0,
                     chunk_costs=None):
        """Replay the §4.2 clock charges for the stage's inner steps and land
        the whole stage in the trace with one Trace.extend call.

        ``eval_charge`` > 0 bills one eval pass of that many points after
        each chunk — the variance-trigger probe (charged like DSM's norm
        test and TwoTrack's condition eval; measurement f̂ evals stay free).

        ``chunk_costs`` (parallel to ``rec.chunk_lengths()``) carries each
        chunk's examples-per-step when it ran on a sweep segment instead of
        the whole window; ``None`` entries charge the full ``n_t``."""
        self._collect_host_records(ctx, info)
        clock, cost, trace = ctx["clock"], ctx["cost"], ctx["trace"]
        fs, ffull = rec.f_window(), rec.f_full()
        n = len(fs)
        times = np.empty(n)
        accs = np.empty(n, dtype=np.int64)
        touched = 0
        i = 0
        for ci, clen in enumerate(rec.chunk_lengths()):
            chunk_n = info.n_t
            if chunk_costs and ci < len(chunk_costs) \
                    and chunk_costs[ci] is not None:
                chunk_n = int(chunk_costs[ci])
            for j in range(clen):
                clock.batch_update(cost(chunk_n))
                touched += cost(chunk_n)
                if eval_charge and j == clen - 1:
                    clock.eval_pass(min(eval_charge, chunk_n))
                    touched += min(eval_charge, chunk_n)
                times[i], accs[i] = clock.time, clock.data_accesses
                i += 1
        self._note_access(ctx, touched)
        every = max(1, int(policy.record_every))
        idx = [i for i in range(n) if i % every == 0 or i == n - 1]
        extras = None
        if ctx["probe"] is not None or extra_base:
            extras = [dict(extra_base or {}) for _ in idx]
            if ctx["probe"] is not None:
                for j, i in enumerate(idx):
                    extras[j]["probe"] = float(ctx["probe"](rec.param_at(i)))
        new = trace.extend(
            step=[ctx["step_count"] + i for i in idx], stage=info.stage,
            window=info.n_t, time=times[idx], accesses=accs[idx],
            f_window=fs[idx], f_full=ffull[idx], extra=extras)
        ctx["step_count"] += n
        ctx["stages"] += 1
        self._emit_stage_totals(ctx, info, steps=n, touched=touched)
        if ctx["progress"]:
            for p in new:
                ctx["progress"](p)

    def _emit_stage_totals(self, ctx, info: StageInfo, *, steps: int,
                           touched: int) -> None:
        """One ``stage.totals`` counter per stage: the cumulative clock and
        engine state the RunReport differences into per-stage rows."""
        if self.recorder is None:
            return
        clock = ctx["clock"]
        self.recorder.counter(
            "stage.totals", tags={"stage": info.stage}, window=info.n_t,
            steps=steps, touched=touched, time=clock.time,
            accesses=clock.data_accesses, loaded=clock.points_loaded,
            transfers=ctx["transfers"], stages=ctx["stages"])

    @staticmethod
    def _note_access(ctx, examples: int) -> None:
        """Report optimizer touches to the data plane's DataAccessMeter, in
        the same units the SimulatedClock charges — real-read accounting."""
        note = getattr(ctx["dataset"], "note_access", None)
        if note is not None and examples:
            note(examples)

    # ------------------------------------------------------- two-track stages
    def _run_two_track(self, ctx, dataset, optimizer, objective,
                       policy: TwoTrack, w, state, full_data, *,
                       first_stage: int = 0):
        clock, cost, trace = ctx["clock"], ctx["cost"], ctx["trace"]
        collect_params = ctx["probe"] is not None
        kernel = _two_track_kernel(optimizer, objective,
                                   condition_eval=policy.condition == "eval",
                                   collect_params=collect_params)
        N = dataset.n
        *racing, final_info = self.stage_infos(policy, N)
        obs = self.recorder
        for info in racing:
            stage = info.stage
            if stage < first_stage:
                continue                # completed before the checkpoint
            n_prev, n_t, n_next = info.n_prev, info.n_t, info.n_next
            if obs is not None:
                obs.set_context(stage=stage)
                obs.instant("stage.begin", window=n_t, n_next=n_next,
                            final=info.is_final)
            with obs_span(obs, "stage.acquire", window=n_t):
                win_t = self._acquire_window(dataset, n_t, n_next)
                win_prev = dataset.window(n_prev)  # resident prefix: no loads
            if self.wait_on_expand:
                clock.wait_for(n_t)
            st_slow = optimizer.reset_memory(
                state if self.carry_state else optimizer.init(w))
            st_fast = optimizer.init(w)
            policy.stage_begin(info)
            probe_k = min(int(policy.probe), n_t) \
                if policy.wants_variance else 0
            rec = StageRecords()
            fast_hist: list[np.ndarray] = []
            # race rounds: plain TwoTrack always confirms after one round
            # (its trigger fired on device, or max_stage_iters elapsed); a
            # ComposedPolicy veto can hold the stage open, re-racing from
            # the current point with a fresh fast track
            while True:
                if self.profiler is not None and rec.steps == 0:
                    self.profiler.observe(
                        info, kernel,
                        (w, st_slow, st_fast, win_t, win_prev, full_data),
                        {"max_iters": int(policy.max_stage_iters)})
                with obs_span(obs, "stage.compute", window=n_t):
                    out = kernel(w, st_slow, st_fast, win_t, win_prev,
                                 full_data,
                                 max_iters=int(policy.max_stage_iters))
                    w, state = out["params"], out["state"]
                    pulled = jax.device_get(
                        {n: v for n, v in out.items()
                         if n not in ("params", "state")})
                ctx["transfers"] += 1
                if obs is not None:
                    obs.instant("engine.transfer",
                                transfers=ctx["transfers"])
                s = int(pulled["s"])
                rec.add_chunk(pulled["f_slow"][:s], pulled["f_full"][:s],
                              jax.tree_util.tree_map(lambda b: b[:s],
                                                     pulled["W"])
                              if collect_params else None)
                fast_hist.append(pulled["f_fast"][:s])
                rec.f_fast_on_t = np.concatenate(fast_hist)
                rec.triggered = bool(pulled["triggered"])
                if policy.wants_variance:
                    v, g2 = jax.device_get(cached_variance(objective)(
                        w, win_t, probe_k))
                    ctx["transfers"] += 1
                    if obs is not None:
                        obs.instant("engine.transfer",
                                    transfers=ctx["transfers"])
                    rec.var, rec.g2 = float(v), float(g2)
                expand = policy.should_expand(info, rec)
                if obs is not None:
                    fs = rec.f_fast_on_t
                    obs.instant("expand.decision", expand=bool(expand),
                                window=n_t, steps=rec.steps, var=rec.var,
                                g2=rec.g2, triggered=rec.triggered,
                                f_last=float(fs[-1]) if len(fs) else None)
                if expand:
                    break
                if rec.steps > self.max_engine_steps:
                    raise RuntimeError(
                        f"policy {policy.name} never expanded after "
                        f"{rec.steps} racing steps")
                st_slow = state
                st_fast = optimizer.init(w)
            s = rec.steps
            with obs_span(obs, "stage.flush", window=n_t):
                self._collect_host_records(ctx, info)
                # replay the per-step clock charges: slow update, fast
                # update, condition evaluation (charged per the paper unless
                # disabled), plus one variance-probe eval at each race-round
                # boundary
                times = np.empty(s)
                accs = np.empty(s, dtype=np.int64)
                touched = 0
                i = 0
                for clen in rec.chunk_lengths():
                    for j in range(clen):
                        clock.batch_update(cost(n_t))
                        clock.batch_update(cost(n_prev))
                        touched += cost(n_t) + cost(n_prev)
                        if policy.charge_condition_eval:
                            clock.eval_pass(cost(n_t))
                            touched += cost(n_t)
                        if probe_k and j == clen - 1:
                            clock.eval_pass(probe_k)
                            touched += probe_k
                        times[i], accs[i] = clock.time, clock.data_accesses
                        i += 1
                self._note_access(ctx, touched)
                extras = [{"f_fast_on_t": float(rec.f_fast_on_t[i])}
                          for i in range(s)]
                if ctx["probe"] is not None:
                    for i in range(s):
                        extras[i]["probe"] = float(
                            ctx["probe"](rec.param_at(i)))
                new = trace.extend(
                    step=np.arange(ctx["step_count"], ctx["step_count"] + s),
                    stage=stage, window=n_t, time=times, accesses=accs,
                    f_window=rec.f_window(), f_full=rec.f_full(),
                    extra=extras)
                ctx["step_count"] += s
                ctx["stages"] += 1
                self._emit_stage_totals(ctx, info, steps=s, touched=touched)
            if ctx["progress"]:
                for p in new:
                    ctx["progress"](p)
            policy.stage_end(info, rec)
            self._stage_boundary(ctx, info, w, state)
            if obs is not None:
                obs.instant("stage.end", window=n_t)
                obs.clear_context("stage")

        # final phase: full window until the step budget is spent
        if first_stage > final_info.stage:
            return w, state             # checkpoint already past the final phase
        info = final_info
        state = optimizer.reset_memory(
            state if self.carry_state else optimizer.init(w))
        w, state = self._run_scan_stage(
            ctx, dataset, optimizer, objective, policy, info, w, state,
            full_data, eval_full=policy.final_eval_full)
        return w, state
