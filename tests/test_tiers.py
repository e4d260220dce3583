"""Tiered corpus invariants (tier1): HBM-budgeted hot windows over the
host-RAM ring and disk shards — shard-aligned budget geometry, append
regime bit-exact vs the untiered plane, disjoint rotation sweeps with
zero resident re-upload and one disk read per example, bounded-ring spill
then bit-exact re-promotion, double-buffered staging, tier-state
checkpoints with HBM-bounded recovery I/O, shard-parallel checkpoint lane
slices, prefetcher backpressure, and the TieringSpec validation gate."""
import time

import numpy as np
import pytest

from repro.api import (DataSpec, OptimizerSpec, PolicySpec, RunSpec,
                       ScheduleSpec, SpecError, TieringSpec, TopologySpec,
                       build, optimizer_spec_of)
from repro.core import BETSchedule, BetEngine, FixedSteps, SimulatedClock
from repro.data import (DataAccessMeter, InMemoryShardStore, Prefetcher,
                        RingTierManager, StreamingDataset, ThrottledStore,
                        TieredCorpus)
from repro.data.synthetic import make_classification
from repro.data.tiers.ckpt import (is_lane_pointer, load_lane_slices,
                                   unlink_lane_slices, write_lane_slices)
from repro.dist import distributed_objective, l2_regularizer
from repro.elastic import (ElasticBetEngine, ElasticDataset,
                           StageCheckpointer, dataset_state, peek_stage_meta,
                           restore_dataset)
from repro.models.linear import (init_params, make_example_losses,
                                 make_objective)
from repro.optim import NewtonCG

pytestmark = pytest.mark.tier1

LAM = 1e-3
SHARD = 16


def problem(n=256, d=8, seed=0):
    ds = make_classification("tiers_t", n=n, d=d, seed=seed)
    return (np.asarray(ds.X), np.asarray(ds.y),
            make_objective("squared_hinge", lam=LAM), init_params(d))


def row_bytes(X, y):
    return X.dtype.itemsize * X.shape[1] + y.dtype.itemsize


def tiered(X, y, *, hbm_rows, shard=SHARD, **kw):
    return TieredCorpus([InMemoryShardStore(X, shard),
                         InMemoryShardStore(y, shard)],
                        hbm_bytes=hbm_rows * row_bytes(X, y), **kw)


# ------------------------------------------------------------------ manager
def test_manager_hot_cap_shard_aligned_and_tiling_disjoint():
    m = RingTierManager(hbm_bytes=100 * 36, row_bytes=36, shard_size=16,
                        capacity=256)
    assert m.hot_cap == 96                       # 100 rows aligned down
    assert not m.rotates(96) and m.rotates(97)
    segs = m.segments(250)
    assert segs[0] == (0, 96) and segs[-1] == (192, 250)
    # disjoint in-order cover of [0, n_t): the zero-reupload argument
    assert [lo for lo, _ in segs[1:]] == [hi for _, hi in segs[:-1]]
    assert m.segments(50) == [(0, 50)]
    # budget never exceeds the corpus
    assert RingTierManager(hbm_bytes=10**9, row_bytes=36, shard_size=16,
                           capacity=64).hot_cap == 64
    with pytest.raises(ValueError, match="below one shard"):
        RingTierManager(hbm_bytes=36 * 15, row_bytes=36, shard_size=16,
                        capacity=64)


# ------------------------------------------------------------ append regime
def test_append_regime_bit_exact_and_loads_each_example_once():
    X, y, _, _ = problem(n=128)
    with tiered(X, y, hbm_rows=128) as tc:
        for n_t in (32, 64, 128):
            Xv, yv = tc.window(n_t)
            np.testing.assert_array_equal(np.asarray(Xv), X[:n_t])
            np.testing.assert_array_equal(np.asarray(yv), y[:n_t])
        assert tc.mode == "append"
        assert tc.meter.examples_loaded == 128       # each example once
        assert tc.meter.examples_uploaded == 128
        assert tc.meter.bytes_uploaded == 128 * row_bytes(X, y)
        assert tc.tier_meter.resident_reuploads == 0


def test_append_double_buffers_the_next_expansion():
    X, y, _, _ = problem(n=128)
    with tiered(X, y, hbm_rows=128) as tc:
        tc.begin_stage(64, 128)                  # stages [64, 128) async
        assert tc.tier_meter.staged_segments == 1
        Xv, yv = tc.begin_stage(128)             # lands the staged buffers
        assert tc.tier_meter.staged_commits == 1
        assert tc.tier_meter.direct_builds == 1  # only the cold start
        np.testing.assert_array_equal(np.asarray(Xv), X[:128])
        np.testing.assert_array_equal(np.asarray(yv), y[:128])
        # commit-time metering: staged rows count exactly once
        assert tc.meter.examples_uploaded == 128
        assert tc.meter.bytes_uploaded == 128 * row_bytes(X, y)


def test_engine_tiered_full_budget_bit_exact_vs_streaming_plane():
    X, y, obj, w0 = problem()
    opt = NewtonCG(hessian_fraction=1.0)
    kw = dict(w0=w0, eval_data=(X[:64], y[:64]))
    policy = dict(inner_steps=2, final_steps=4)
    with StreamingDataset([InMemoryShardStore(X, SHARD),
                           InMemoryShardStore(y, SHARD)]) as plane:
        tr_ref = BetEngine(schedule=BETSchedule(n0=64)).run(
            plane, opt, obj, FixedSteps(**policy), clock=SimulatedClock(),
            **kw)
    with tiered(X, y, hbm_rows=len(X)) as tc:
        tr = BetEngine(schedule=BETSchedule(n0=64)).run(
            tc, opt, obj, FixedSteps(**policy), clock=SimulatedClock(), **kw)
        assert tc.mode == "append"
    np.testing.assert_array_equal(tr.column("f_window"),
                                  tr_ref.column("f_window"))
    np.testing.assert_array_equal(tr.column("f_full"),
                                  tr_ref.column("f_full"))


# ---------------------------------------------------------- rotation regime
def test_rotation_sweep_views_bit_exact_and_disjoint():
    X, y, _, _ = problem(n=256)
    with tiered(X, y, hbm_rows=64) as tc:
        tc.begin_stage(64, 128)
        assert tc.mode == "append"

        def check(view, lo, hi):
            Xv, yv = view
            np.testing.assert_array_equal(np.asarray(Xv), X[lo:hi])
            np.testing.assert_array_equal(np.asarray(yv), y[lo:hi])

        # n_t=128 > hot_cap: transition to the 2-segment sweep
        check(tc.begin_stage(128, 256), 0, 64)
        assert tc.mode == "rotate"
        assert tc.segment_steps(128, 2) == [(1, 64), (1, 64)]
        check(tc.advance_window(), 64, 128)
        # n_t=256: mid-sweep position survives (stride alignment), so the
        # sweep resumes at segment 1 and wraps through 0
        check(tc.begin_stage(256), 64, 128)
        assert tc.segment_steps(256, 4) == [(1, 64)] * 4
        for lo in (128, 192, 0):
            check(tc.advance_window(), lo, lo + 64)
        # one disk read per example, zero resident re-upload, no evictions
        assert tc.meter.examples_loaded == 256
        assert tc.tier_meter.resident_reuploads == 0
        assert tc.tier_meter.evictions == 0
        assert tc.ring.resident_shards == 16         # unbounded ring keeps all
        with pytest.raises(RuntimeError, match="eval_data"):
            tc.window(256)                           # no full-window fallback


def test_engine_rotation_run_loads_once_and_never_reuploads_resident():
    X, y, obj, w0 = problem(n=256)
    with tiered(X, y, hbm_rows=64) as tc:
        tr = BetEngine(schedule=BETSchedule(n0=64)).run(
            tc, NewtonCG(hessian_fraction=1.0), obj,
            FixedSteps(inner_steps=4, final_steps=8), w0=w0,
            clock=SimulatedClock(), eval_data=(X[:64], y[:64]))
        assert tc.mode == "rotate"
        assert int(tr.points[-1].window) == 256      # trained to full corpus
        assert tc.meter.examples_loaded == 256       # disk: once per example
        assert tc.meter.examples_uploaded > 256      # device: swept repeatedly
        assert tc.tier_meter.resident_reuploads == 0
        assert tc.tier_meter.staged_commits > 0      # double-buffer engaged
        report = tc.tier_report()
        assert report["mode"] == "rotate" and report["hot_cap"] == 64


def test_bounded_ring_spills_then_repromotes_bit_exact():
    X, y, _, _ = problem(n=256)
    shard_bytes = SHARD * row_bytes(X, y)
    with tiered(X, y, hbm_rows=64, host_bytes=6 * shard_bytes) as tc:
        def sweep(n_t, k):
            # a view lives until the next advance, which donates its buffer
            # to land the next segment in place: read each one before that
            yield tc.begin_stage(n_t)
            for _ in tc.segment_steps(n_t, k)[1:]:
                yield tc.advance_window()

        tc.begin_stage(64, 128)
        tc.begin_stage(128, 256)                 # enter rotation
        for _ in tc.segment_steps(128, 2)[1:]:
            tc.advance_window()
        for _ in sweep(256, 4):
            pass
        assert tc.tier_meter.evictions > 0       # the budget actually bites
        assert tc.ring.resident_bytes <= 6 * shard_bytes + \
            len(tc.ring._protected) * shard_bytes
        loaded_once = tc.meter.examples_loaded
        assert loaded_once >= 256
        # second sweep: spilled shards are fresh disk reads, and the
        # re-promoted rows are still bit-exact
        for (Xv, yv), (lo, hi) in zip(sweep(256, 4),
                                      ((0, 64), (64, 128), (128, 192),
                                       (192, 256))):
            np.testing.assert_array_equal(np.asarray(Xv), X[lo:hi])
            np.testing.assert_array_equal(np.asarray(yv), y[lo:hi])
        assert tc.meter.examples_loaded > loaded_once
        assert tc.tier_meter.resident_reuploads == 0


# --------------------------------------------------------------- checkpoint
def test_tier_state_checkpoint_rewarm_bounded_by_hot_cap():
    X, y, _, _ = problem(n=256)
    with tiered(X, y, hbm_rows=64) as tc:
        tc.begin_stage(64, 128)
        tc.begin_stage(128, 256)
        tc.segment_steps(128, 2)
        tc.advance_window()                      # hot segment = [64, 128)
        state = dataset_state(tc)
        ref = tc.meter.snapshot()
    assert state["kind"] == "tiered"
    assert state["tier"]["mode"] == "rotate"
    with tiered(X, y, hbm_rows=64) as tc2:
        rewarm = restore_dataset(tc2, state, 128)
        # recovery I/O re-lands ONLY the hot window, never the corpus
        assert rewarm["rewarm_examples"] == 64
        assert rewarm["examples_loaded"] == 64
        assert tc2.mode == "rotate" and tc2.hot_range == (64, 128)
        Xv, yv = tc2._view_seg()
        np.testing.assert_array_equal(np.asarray(Xv), X[64:128])
        np.testing.assert_array_equal(np.asarray(yv), y[64:128])
        # meters continue from the checkpointed counters, not the rewarm's
        assert tc2.meter.snapshot() == ref
        assert tc2.tier_meter.snapshot() == state["tier"]["meter"]


def test_restore_rejects_tiered_streaming_mismatch():
    X, y, _, _ = problem(n=64)
    with tiered(X, y, hbm_rows=64) as tc:
        tc.window(64)
        state = dataset_state(tc)
    with StreamingDataset([InMemoryShardStore(X, SHARD),
                           InMemoryShardStore(y, SHARD)]) as plane:
        with pytest.raises(ValueError, match="'tiered'"):
            restore_dataset(plane, state, 64)


def test_kill_resume_tiered_rotation_bit_compatible(tmp_path):
    X, y, obj, w0 = problem(n=256)
    opt = NewtonCG(hessian_fraction=1.0)
    kw = dict(w0=w0, eval_data=(X[:64], y[:64]))
    policy = dict(inner_steps=4, final_steps=8)

    def engine():
        return BetEngine(schedule=BETSchedule(n0=64))

    with tiered(X, y, hbm_rows=64) as tc:
        tr_ref = engine().run(tc, opt, obj, FixedSteps(**policy),
                              clock=SimulatedClock(), **kw)

    class _Killed(Exception):
        pass

    ck = StageCheckpointer(str(tmp_path))

    def die(end):
        ck(end)
        if end.info.stage == 1:
            raise _Killed

    killed = engine()
    killed.stage_callback = die
    with tiered(X, y, hbm_rows=64) as tc:
        with pytest.raises(_Killed):
            killed.run(tc, opt, obj, FixedSteps(**policy),
                       clock=SimulatedClock(), **kw)
    restored = ck.restore(w0, opt.init(w0))
    clock = restored.restore_clock(SimulatedClock())
    with tiered(X, y, hbm_rows=64) as tc:
        rewarm = restored.restore_dataset(tc)
        assert rewarm["rewarm_examples"] <= tc.hot_cap
        tr = engine().run(tc, opt, obj, FixedSteps(**policy),
                          clock=clock, resume=restored.resume,
                          w0=restored.params, opt_state0=restored.opt_state,
                          **{k: v for k, v in kw.items() if k != "w0"})
        # the restart lost the host ring: beyond the hot re-land (charged
        # to rewarm), the resumed sweep re-reads the one segment the ring
        # would have held — 4 shards, bounded by hot_cap, not n
        assert tc.meter.examples_loaded == 256 + 64

    def stitch(col):
        return [p[col] for p in restored.trace_points()] + tr.column(col)

    for col in ("f_window", "f_full"):
        np.testing.assert_array_equal(stitch(col), tr_ref.column(col))
    for col in ("step", "stage", "window", "time", "accesses"):
        assert stitch(col) == tr_ref.column(col)


# ------------------------------------------------- checkpoint lane slices
def test_lane_slice_files_roundtrip_and_cleanup(tmp_path):
    meters = [{"examples_loaded": 10 * i, "bytes_loaded": 100 * i}
              for i in range(5)]
    pointer = write_lane_slices(tmp_path, "stage_0003", meters)
    assert is_lane_pointer(pointer) and not is_lane_pointer(meters)
    names = pointer["lane_files"]
    assert names == [f"stage_0003_lane{i:02d}.json" for i in range(5)]
    assert all((tmp_path / n).exists() for n in names)
    assert load_lane_slices(tmp_path, pointer) == meters
    unlink_lane_slices(tmp_path, "stage_0003")
    assert not list(tmp_path.glob("stage_0003_lane*.json"))


def test_distributed_checkpoint_writes_and_inflates_lane_slices(tmp_path):
    X, y, _, w0 = problem(n=256)
    dobj = distributed_objective(make_example_losses("squared_hinge"),
                                 regularizer=l2_regularizer(LAM))
    opt = NewtonCG(hessian_fraction=1.0)
    ck = StageCheckpointer(str(tmp_path), keep=2)
    engine = ElasticBetEngine(schedule=BETSchedule(n0=32))
    engine.stage_callback = ck
    with ElasticDataset([InMemoryShardStore(X, SHARD),
                         InMemoryShardStore(y, SHARD)], num_hosts=3) as dd:
        engine.run(dd, opt, dobj, FixedSteps(inner_steps=1, final_steps=1),
                   w0=w0, clock=SimulatedClock(), eval_data=(X, y))
        ref = [m.snapshot() for m in dd.host_meters]
    latest = ck.latest()
    # each lane wrote its own slice file; the sidecar keeps only a pointer
    assert len(list(tmp_path.glob(f"{latest.name}_lane*.json"))) == 3
    assert is_lane_pointer(peek_stage_meta(latest)["dataset"]["host_meters"])
    restored = ck.restore(w0, opt.init(w0))
    assert restored.meta["dataset"]["host_meters"] == ref
    # rolling kept 2 checkpoints — rolled stages' lane files are gone too
    kept = {p.stem for p in tmp_path.glob("stage_*.npz")}
    assert len(kept) == 2
    for lane in tmp_path.glob("stage_*_lane*.json"):
        assert lane.name.rsplit("_lane", 1)[0] in kept


# ------------------------------------------------- prefetcher backpressure
def test_hidden_take_records_zero_blocked_time():
    X, _, _, _ = problem(n=64)
    store = ThrottledStore(InMemoryShardStore(X, SHARD), delay_s=0.02)
    meter = DataAccessMeter()
    with Prefetcher([store], meter) as p:
        (rows,) = p.take(0, hidden=True)
        np.testing.assert_array_equal(rows, X[:SHARD])
        assert meter.blocked_time_s == 0.0           # overlapped by contract
        assert meter.load_time_s > 0.0
        (rows,) = p.take(1)                          # demand take still blocks
        assert meter.blocked_time_s > 0.0


def test_max_inflight_backpressures_hints_not_demand():
    X, _, _, _ = problem(n=128)
    store = ThrottledStore(InMemoryShardStore(X, SHARD), delay_s=0.05)
    with pytest.raises(ValueError, match="max_inflight"):
        Prefetcher([store], max_inflight=0)
    with Prefetcher([store], max_inflight=2) as p:
        p.schedule([0, 1, 2, 3])
        assert p.inflight() == 2                     # the bound holds
        assert p.scheduled() == [0, 1, 2, 3]         # hints are not dropped
        (rows,) = p.take(0)                          # frees a slot -> pump
        np.testing.assert_array_equal(rows, X[:SHARD])
        assert p.inflight() <= 2
        (rows,) = p.take(3)                          # backlogged: demand load
        np.testing.assert_array_equal(rows, X[3 * SHARD: 4 * SHARD])
        for i in (1, 2):
            (rows,) = p.take(i)
            np.testing.assert_array_equal(
                rows, X[i * SHARD: (i + 1) * SHARD])
        assert p.scheduled() == []


def test_tiered_corpus_threads_max_inflight_through():
    X, y, _, _ = problem(n=128)
    with tiered(X, y, hbm_rows=128, max_inflight=3) as tc:
        assert tc.prefetcher.max_inflight == 3
        tc.window(128)
        assert tc.prefetcher.inflight() == 0         # everything drained


# --------------------------------------------------------------- spec gate
def _tiered_spec(data_kw=None, **tiering):
    kw = dict(dataset="w8a_like", scale=0.02, plane="plane",
              store="memory", shard_size=64, tiering=TieringSpec(**tiering))
    kw.update(data_kw or {})
    data = DataSpec(**kw)
    return RunSpec(
        data=data,
        policy=PolicySpec("fixed_steps", {"inner_steps": 2,
                                          "final_steps": 4}),
        optimizer=OptimizerSpec("newton_cg", {"hessian_fraction": 1.0}),
        schedule=ScheduleSpec(n0=128))


def test_tiering_spec_validation_rejects_bad_combos():
    with pytest.raises(SpecError, match="streaming plane"):
        build(_tiered_spec({"plane": "host"}, enabled=True,
                           hbm_bytes=1 << 20))
    with pytest.raises(SpecError, match="hbm_bytes"):
        build(_tiered_spec(enabled=True))
    with pytest.raises(SpecError, match="max_inflight"):
        build(_tiered_spec(enabled=True, hbm_bytes=1 << 20, max_inflight=0))
    with pytest.raises(SpecError, match="single-host"):
        build(_tiered_spec(enabled=True, hbm_bytes=1 << 20).replace(
            topology=TopologySpec(hosts=2)))
    with pytest.raises(SpecError, match="enabled=False"):
        build(_tiered_spec(hbm_bytes=1 << 20))
    with pytest.raises(SpecError, match="unknown tier manager"):
        build(_tiered_spec(enabled=True, hbm_bytes=1 << 20,
                           manager="nonesuch"))
    with pytest.raises(SpecError, match="two_track"):
        build(_tiered_spec(enabled=True, hbm_bytes=1 << 20).replace(
            policy=PolicySpec("two_track", {"final_steps": 4})))


def test_session_tiered_rotation_run_end_to_end():
    # w8a_like @0.02 is 163 rows of 1204 bytes; one 64-row shard of budget
    # forces the rotation sweep (3 segments at the final stage)
    spec = _tiered_spec(enabled=True, hbm_bytes=64 * 1204)
    session = build(spec)
    assert isinstance(session.dataset, TieredCorpus)
    trace = session.run()
    assert int(trace.points[-1].window) == session.dataset.n
    assert trace.meta["tiers"]["mode"] == "rotate"
    meters = session.meters
    assert meters["tiers"]["resident_reuploads"] == 0
    assert meters["tiers"]["staged_commits"] > 0
