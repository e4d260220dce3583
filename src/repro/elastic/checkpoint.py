"""Stage-boundary checkpoints: the *full* BET runtime state.

A resumable BET run needs more than (params, opt_state): the window cursor
``(stage, n_t, step)``, the simulated clock, the per-lane
``DataAccessMeter`` counters, the trace so far, and — elastically — the
lane→worker assignment and owned-shard lists after any deltas.  Because the
window is a prefix of one fixed permutation, that is *everything*: a fresh
process re-reads the ``[0, n_t)`` prefix (charged to a separate "rewarm"
record so the restored Thm 4.1 counters stay bit-compatible with the
uninterrupted run) and continues the schedule from ``stage + 1`` with
identical numerics and identical accounting.

``StageCheckpointer`` plugs into ``BetEngine.stage_callback`` — the
checkpoint always lands at a stage boundary, where (params, opt_state) are
the exact carries the next stage starts from.
"""
from __future__ import annotations

import dataclasses
import json
import os
import pathlib

from ..checkpoint.ckpt import load_state, save_state
from ..core.engine import ResumeState, StageEnd
from ..core.timemodel import SimulatedClock
from ..data.plane import StreamingDataset
from ..data.tiers import TieredCorpus
from ..data.tiers.ckpt import (is_lane_pointer, load_lane_slices,
                               unlink_lane_slices, write_lane_slices)
from ..dist.runtime import DistributedDataset
from ..obs.events import obs_span


# ------------------------------------------------------------ dataset state
def dataset_state(dataset) -> dict:
    """JSON-safe runtime state of any dataset flavor: meter counters plus
    window cursors (and the elastic maps when present)."""
    state: dict = {}
    if isinstance(dataset, DistributedDataset):
        state["kind"] = "distributed"
        state["host_meters"] = [m.snapshot() for m in dataset.host_meters]
        state["access_meter"] = dataset._access.snapshot()
        state["window_cursor"] = dataset.stacked[0].cursor()
        elastic = getattr(dataset, "elastic_state", None)
        if elastic is not None:
            state["elastic"] = elastic()
    elif isinstance(dataset, TieredCorpus):
        state["kind"] = "tiered"
        state["meter"] = dataset.meter.snapshot()
        state["tier"] = dataset.tier_state()
    elif isinstance(dataset, StreamingDataset):
        state["kind"] = "streaming"
        state["meter"] = dataset.meter.snapshot()
        state["window_cursor"] = dataset.windows[0].cursor() \
            if hasattr(dataset.windows[0], "cursor") else None
    else:
        state["kind"] = "plain"         # host-resident: nothing to capture
    return state


def _dataset_kind(dataset) -> str:
    if isinstance(dataset, DistributedDataset):
        return "distributed"
    if isinstance(dataset, TieredCorpus):
        return "tiered"
    if isinstance(dataset, StreamingDataset):
        return "streaming"
    return "plain"


def restore_dataset(dataset, state: dict, n_t: int) -> dict:
    """Bring a *freshly constructed* dataset to the checkpointed state.

    Order matters: (1) elastic maps first, so lanes rebuild under the
    checkpointed ownership; (2) re-land the resident prefix ``[0, n_t)``
    (real storage reads), cross-checked against the checkpointed window
    cursor; (3) capture that restart I/O as the returned ``rewarm``
    record; (4) overwrite the meters with the checkpointed counters — the
    resumed accounting continues exactly where the uninterrupted run would
    be, with the restart cost reported separately instead of silently
    double-counted."""
    kind = state.get("kind", "plain")
    have = _dataset_kind(dataset)
    if kind != have:
        raise ValueError(
            f"checkpoint was taken on a {kind!r} dataset but the resume "
            f"constructed a {have!r} one ({type(dataset).__name__}) — "
            f"meters/cursors would be silently mismatched; resume with the "
            f"same --hosts / data-plane configuration")
    if kind == "plain":
        return {}
    if kind == "distributed":
        if "elastic" in state:
            restore = getattr(dataset, "restore_elastic_state", None)
            if restore is None:
                raise ValueError(
                    "checkpoint carries elastic state but the dataset is "
                    f"a plain {type(dataset).__name__}")
            restore(state["elastic"])
        dataset.window(n_t)
        _check_cursor(state["window_cursor"],
                      dataset.stacked[0].cursor(), n_t)
        rewarm = dataset.meter.snapshot()
        for m, snap in zip(dataset.host_meters, state["host_meters"]):
            m.restore(snap)
        dataset._access.restore(state["access_meter"])
        return rewarm
    if kind == "tiered":
        # re-land ONLY the checkpointed hot window (recovery I/O bounded by
        # the HBM budget, not n_t), then the usual rewarm/restore split
        reland = dataset.restore_tier(state["tier"])
        rewarm = dataset.meter.snapshot()
        rewarm.update(reland)
        dataset.meter.restore(state["meter"])
        dataset.tier_meter.restore(state["tier"]["meter"])
        return rewarm
    dataset.window(n_t)
    _check_cursor(state["window_cursor"],
                  dataset.windows[0].cursor(), n_t)
    rewarm = dataset.meter.snapshot()
    dataset.meter.restore(state["meter"])
    return rewarm


def _check_cursor(saved, rebuilt, n_t: int) -> None:
    """The re-warmed residency must land within the checkpointed cursor.

    Equality is the normal case; the checkpointed run may legitimately
    have been resident *beyond* ``n_t`` (e.g. a full-corpus eval view
    forced residency), which the resumed ``run()`` re-establishes itself.
    But a rewarm that *overshoots* the saved cursor means the resumed
    dataset was built over different shards/ownership — its 'resident'
    window would silently diverge from the permutation prefix the
    schedule believes is loaded."""
    if saved is None or rebuilt is None:
        return
    s = saved.get("counts", [saved.get("n_valid")])
    r = rebuilt.get("counts", [rebuilt.get("n_valid")])
    if len(s) != len(r) or any(ri > si for si, ri in zip(s, r)):
        raise ValueError(
            f"re-warmed window cursor {rebuilt} overshoots the "
            f"checkpointed cursor {saved} at n_t={n_t}: the resumed "
            f"dataset's sharding/ownership differs from the checkpointed "
            f"run's")


def _point_dicts(trace) -> list[dict]:
    return [{"step": p.step, "stage": p.stage, "window": p.window,
             "time": p.time, "accesses": p.accesses,
             "f_window": p.f_window, "f_full": p.f_full, "extra": p.extra}
            for p in trace.points]


# ------------------------------------------------------------- checkpointer
@dataclasses.dataclass
class StageCheckpointer:
    """Rolling stage-boundary checkpoints; plugs into
    ``BetEngine.stage_callback``.  ``every`` thins the cadence (checkpoint
    after stages 0, every, 2*every, ...); the final stage always saves.
    ``spec`` (a ``RunSpec.to_dict()``) is saved into every checkpoint's
    meta, making the checkpoint a self-describing, re-buildable artifact."""
    directory: str
    keep: int = 3
    every: int = 1
    spec: dict | None = None
    # observability: when an EventRecorder is wired, every publish emits one
    # ``checkpoint.publish`` span covering the atomic write
    recorder: object = None

    def __post_init__(self):
        if self.keep < 1:
            raise ValueError(f"keep must be >= 1, got {self.keep}")
        if self.every < 1:
            raise ValueError(f"every must be >= 1, got {self.every}")
        self.saved: list[int] = []

    def __call__(self, end: StageEnd) -> None:
        if end.info.stage % self.every and not end.info.is_final:
            return
        self.save(end)

    def save(self, end: StageEnd) -> pathlib.Path:
        with obs_span(self.recorder, "checkpoint.publish",
                      stage=end.info.stage, n_t=end.info.n_t):
            return self._save(end)

    def _save(self, end: StageEnd) -> pathlib.Path:
        d = pathlib.Path(self.directory)
        path = d / f"stage_{end.info.stage:04d}"
        # publish atomically: write under a dot-prefixed temp name (invisible
        # to the stage_*.npz glob), then os.replace into place — a concurrent
        # reader (the hot-swap server, serve/swap.py) either sees the full
        # checkpoint or none of it.  The .json lands before the .npz because
        # readers key on the .npz: once it appears, its sidecar exists.
        tmp = d / f".tmp_{path.name}"
        meta = {
            "cursor": {"stage": end.info.stage, "n_t": end.info.n_t,
                       "n_next": end.info.n_next, "step": end.step_count,
                       "stages": end.stages, "transfers": end.transfers},
            "clock": end.clock.snapshot(),
            "dataset": dataset_state(end.dataset),
            "trace": {"method": end.trace.method,
                      "points": _point_dicts(end.trace)},
        }
        if self.spec is not None:
            meta["spec"] = self.spec
        ds_state = meta["dataset"]
        if ds_state.get("kind") == "distributed" and "host_meters" in ds_state:
            # shard-parallel save: each lane writes its own slice file and
            # the sidecar keeps a pointer; lanes land before the .npz is
            # published so readers (which key on the .npz) never see a
            # checkpoint whose lanes are missing
            ds_state["host_meters"] = write_lane_slices(
                d, path.name, ds_state["host_meters"])
        save_state(tmp, {"params": end.params, "opt": end.opt_state},
                   meta=meta)
        os.replace(tmp.with_suffix(".json"), path.with_suffix(".json"))
        os.replace(tmp.with_suffix(".npz"), path.with_suffix(".npz"))
        self.saved.append(end.info.stage)
        ckpts = sorted(d.glob("stage_*.npz"))
        for old in ckpts[: -self.keep]:
            old.unlink(missing_ok=True)
            old.with_suffix(".json").unlink(missing_ok=True)
            unlink_lane_slices(d, old.stem)
        return path

    def latest(self) -> pathlib.Path | None:
        ckpts = sorted(pathlib.Path(self.directory).glob("stage_*.npz"))
        return ckpts[-1].with_suffix("") if ckpts else None

    def restore(self, params_like, opt_like=None) -> "RestoredRun | None":
        latest = self.latest()
        if latest is None:
            return None
        return load_stage_checkpoint(latest, params_like, opt_like)


def peek_stage_meta(path) -> dict:
    """A stage checkpoint's sidecar metadata (cursor/clock/dataset/spec)
    without loading any arrays — spec validation and the hot-swap server's
    staleness bookkeeping read this."""
    sidecar = json.loads(pathlib.Path(path).with_suffix(".json").read_text())
    return sidecar["meta"]


def load_stage_checkpoint(path, params_like, opt_like=None) -> "RestoredRun":
    trees, meta = load_state(path, {"params": params_like, "opt": opt_like})
    ds_state = meta.get("dataset") or {}
    if is_lane_pointer(ds_state.get("host_meters")):
        ds_state["host_meters"] = load_lane_slices(
            pathlib.Path(path).parent, ds_state["host_meters"])
    return RestoredRun(params=trees["params"], opt_state=trees["opt"],
                       meta=meta)


@dataclasses.dataclass
class RestoredRun:
    """A loaded stage checkpoint plus the helpers a resume needs."""
    params: object
    opt_state: object
    meta: dict

    @property
    def resume(self) -> ResumeState:
        c = self.meta["cursor"]
        return ResumeState(next_stage=c["stage"] + 1, step_count=c["step"],
                           stages=c["stages"], transfers=c["transfers"])

    @property
    def n_t(self) -> int:
        return int(self.meta["cursor"]["n_t"])

    def restore_clock(self, clock: SimulatedClock) -> SimulatedClock:
        clock.restore(self.meta["clock"])
        return clock

    def restore_dataset(self, dataset) -> dict:
        """Re-land the resident window and restore meters; returns the
        rewarm I/O record (see ``restore_dataset``)."""
        return restore_dataset(dataset, self.meta["dataset"], self.n_t)

    def trace_points(self) -> list[dict]:
        """The pre-checkpoint trajectory, for stitching a resumed trace
        against an uninterrupted reference."""
        return list(self.meta["trace"]["points"])
