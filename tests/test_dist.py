"""Multi-host BET runtime invariants (tier1): shard ownership prefix
algebra, owned-shard stores, the stacked SPMD window, distributed-vs-single
engine parity on the convex path, collective stage flush accounting, the
distributed LM path, and mesh construction validation.  A subprocess test
exercises the real forced-host-platform device mesh
(XLA_FLAGS=--xla_force_host_platform_device_count=4)."""
import os
import subprocess
import sys
import textwrap

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import BETSchedule, BetEngine, FixedSteps, GradientVariance, \
    SimulatedClock, TwoTrack
from repro.data import HostWindows, InMemoryShardStore, StackedDeviceWindow
from repro.data.synthetic import make_classification
from repro.dist import (DistributedBetEngine, DistributedDataset,
                        OwnedShardStore, ShardOwnership, SimulatedTopology,
                        distributed_objective, l2_regularizer)
from repro.launch.mesh import make_host_mesh, make_hosts_mesh
from repro.models.linear import (init_params, make_example_losses,
                                 make_objective)
from repro.optim import NewtonCG

pytestmark = pytest.mark.tier1

LAM = 1e-3


def small_problem(n=384, d=24, seed=0):
    ds = make_classification("dist_t", n=n, d=d, seed=seed)
    obj = make_objective("squared_hinge", lam=LAM)
    dobj = distributed_objective(make_example_losses("squared_hinge"),
                                 regularizer=l2_regularizer(LAM))
    return ds, obj, dobj, init_params(ds.d)


# ----------------------------------------------------------------- ownership
def test_ownership_validates_construction():
    with pytest.raises(ValueError):
        ShardOwnership(num_shards=2, num_hosts=3, shard_size=4,
                       num_examples=8)          # more hosts than shards
    with pytest.raises(ValueError):
        ShardOwnership(num_shards=3, num_hosts=2, shard_size=4,
                       num_examples=8)          # inconsistent shard count
    with pytest.raises(ValueError):
        ShardOwnership(num_shards=4, num_hosts=2, shard_size=4,
                       num_examples=16, strategy="mystery")


@pytest.mark.parametrize("strategy", ["striped", "blocked"])
def test_ownership_partitions_shards_and_examples(strategy):
    own = ShardOwnership(num_shards=7, num_hosts=3, shard_size=5,
                         num_examples=33, strategy=strategy)   # ragged tail
    ids = np.concatenate([own.owned_shards(h) for h in range(3)])
    assert sorted(ids.tolist()) == list(range(7))
    ex = np.concatenate([own.local_to_global(h) for h in range(3)])
    assert np.array_equal(np.sort(ex), np.arange(33))
    assert sum(own.num_owned_examples(h) for h in range(3)) == 33
    # prefix algebra: shares sum to n and are monotone per host
    prev = [0, 0, 0]
    for n in range(0, 40):
        ms = [own.examples_in_prefix(h, n) for h in range(3)]
        assert sum(ms) == min(n, 33)
        assert all(a <= b for a, b in zip(prev, ms))
        prev = ms


def test_striped_ownership_balances_every_prefix():
    own = ShardOwnership(num_shards=16, num_hosts=4, shard_size=8,
                         num_examples=128)
    for n in (0, 7, 8, 33, 64, 100, 128):
        ms = [own.examples_in_prefix(h, n) for h in range(4)]
        assert max(ms) - min(ms) <= own.shard_size


def test_owned_store_reads_only_owned_shards():
    data = np.arange(66, dtype=np.float32).reshape(33, 2)
    inner = InMemoryShardStore(data, 5)
    reads = []
    orig = inner.load
    inner.load = lambda s: (reads.append(s), orig(s))[1]
    own = ShardOwnership.for_store(inner, 3)
    stores = [OwnedShardStore(inner, own, h) for h in range(3)]
    # local stores partition the corpus and only touch owned global shards
    for h, s in enumerate(stores):
        local = np.concatenate([s.load(j) for j in range(s.num_shards)])
        np.testing.assert_array_equal(local, data[own.local_to_global(h)])
    assert sorted(reads) == list(range(7))
    assert sum(s.num_examples for s in stores) == 33
    with pytest.raises(ValueError):
        OwnedShardStore(InMemoryShardStore(data, 4), own, 0)  # size mismatch


# ------------------------------------------------------------ stacked window
def test_stacked_window_lane_growth_and_metering():
    from repro.data import DataAccessMeter
    meters = tuple(DataAccessMeter() for _ in range(2))
    sw = StackedDeviceWindow(num_hosts=2, capacity=6, item_shape=(3,),
                             dtype=np.float32, meters=meters)
    a = np.ones((4, 3), np.float32)
    sw.append(0, a)
    sw.append(1, 2 * a[:2])
    assert sw.counts.tolist() == [4, 2]
    buf = np.asarray(sw.buffer)
    np.testing.assert_array_equal(buf[0, :4], a)
    np.testing.assert_array_equal(buf[1, :2], 2 * a[:2])
    assert buf[0, 4:].sum() == 0 and buf[1, 2:].sum() == 0
    assert meters[0].examples_uploaded == 4
    assert meters[1].examples_uploaded == 2
    with pytest.raises(ValueError):
        sw.append(0, np.ones((3, 3), np.float32))     # lane overflow
    with pytest.raises(ValueError):
        sw.append(1, np.ones((1, 2), np.float32))     # item shape
    with pytest.raises(IndexError):
        sw.append(2, a)


# -------------------------------------------------------- distributed dataset
def test_distributed_dataset_views_match_ownership_partition():
    ds, _, _, _ = small_problem(n=96, d=4)
    X, y = np.asarray(ds.X), np.asarray(ds.y)
    with DistributedDataset([InMemoryShardStore(X, 16),
                             InMemoryShardStore(y, 16)],
                            num_hosts=3) as dd:
        ref = dd.ownership.partition((X, y))
        for n_t in (16, 48, 96):
            hw = dd.window(n_t)
            assert isinstance(hw, HostWindows)
            assert int(jnp.sum(hw.counts)) == n_t
            for h in range(3):
                m = int(hw.counts[h])
                # valid prefixes are exactly the owned slice of [0, n_t)
                np.testing.assert_array_equal(
                    np.asarray(hw.fields[0][h][:m]),
                    np.asarray(ref.fields[0][h][:m]))
        # full residency: every host loaded exactly its owned examples, once
        assert [dd.host_meters[h].examples_loaded for h in range(3)] == \
               [dd.ownership.num_owned_examples(h) for h in range(3)]
        up0 = [dd.host_meters[h].bytes_uploaded for h in range(3)]
        dd.window(96)                       # same window: nothing moves
        assert [dd.host_meters[h].bytes_uploaded for h in range(3)] == up0


def test_distributed_objective_matches_plain_on_same_data():
    ds, obj, dobj, w0 = small_problem(n=128, d=8)
    X, y = np.asarray(ds.X), np.asarray(ds.y)
    own = ShardOwnership(num_shards=8, num_hosts=3, shard_size=16,
                         num_examples=128)
    hw = own.partition((X, y))
    w = w0 + 0.05
    f_plain = float(obj(w, (ds.X, ds.y)))
    f_dist = float(dobj(w, hw))
    assert f_plain == pytest.approx(f_dist, rel=1e-5)
    # plain-data fallback serves host-resident eval sets identically
    assert float(dobj(w, (ds.X, ds.y))) == pytest.approx(f_plain, rel=1e-6)


# ------------------------------------------------------------ engine parity
def test_distributed_engine_parity_and_accounting():
    """DistributedBetEngine over 3 hosts vs BetEngine single-host on the
    same permutation: identical stage structure, trajectories within fp
    tolerance (psum reassociates the fp32 reduction — stated reason), every
    host loads only its owned slice, global accesses equal the clock's
    Thm 4.1 charges, and the stage flush stays one transfer per stage."""
    ds, obj, dobj, w0 = small_problem()
    X, y = np.asarray(ds.X), np.asarray(ds.y)
    opt = NewtonCG(hessian_fraction=1.0)
    sched = BETSchedule(n0=48)
    kw = dict(inner_steps=2, final_steps=4)
    eval_data = (ds.X, ds.y)

    tr_host = BetEngine(schedule=sched).run(
        ds, opt, obj, FixedSteps(**kw), w0=w0, clock=SimulatedClock(),
        eval_data=eval_data)

    clock = SimulatedClock()
    with DistributedDataset([InMemoryShardStore(X, 32),
                             InMemoryShardStore(y, 32)],
                            num_hosts=3) as dd:
        tr_dist = DistributedBetEngine(schedule=sched).run(
            dd, opt, dobj, FixedSteps(**kw), w0=w0, clock=clock,
            eval_data=eval_data)

        assert [(p.stage, p.window) for p in tr_host.points] == \
               [(p.stage, p.window) for p in tr_dist.points]
        np.testing.assert_allclose(tr_host.column("f_window"),
                                   tr_dist.column("f_window"),
                                   rtol=1e-3, atol=1e-6)
        np.testing.assert_allclose(tr_host.column("f_full"),
                                   tr_dist.column("f_full"),
                                   rtol=1e-3, atol=1e-6)
        # clock columns are charged identically
        assert tr_host.column("time") == tr_dist.column("time")
        assert tr_host.column("accesses") == tr_dist.column("accesses")
        # per-host loads: the owned slice, nothing else, each example once
        assert [dd.host_meters[h].examples_loaded for h in range(3)] == \
               [dd.ownership.num_owned_examples(h) for h in range(3)]
        assert dd.meter.examples_loaded == ds.n
        assert dd.meter.examples_accessed == clock.data_accesses
        # ≤ 1 host transfer per stage; the collective flush rode on it
        assert tr_dist.meta["host_transfers"] <= tr_dist.meta["stages"]
        recs = tr_dist.meta["host_stage_records"]
        assert [r["stage"] for r in recs] == \
               sorted({p.stage for p in tr_dist.points})
        assert all(len(r["hosts"]) == 3 for r in recs)
        assert tr_dist.meta["dist"]["meter"]["examples_loaded"] == ds.n


def test_distributed_two_track_runs_device_side():
    ds, obj, dobj, w0 = small_problem(n=256, d=16)
    X, y = np.asarray(ds.X), np.asarray(ds.y)
    opt = NewtonCG(hessian_fraction=1.0)
    with DistributedDataset([InMemoryShardStore(X, 32),
                             InMemoryShardStore(y, 32)],
                            num_hosts=2) as dd:
        tr = DistributedBetEngine(schedule=BETSchedule(n0=64)).run(
            dd, opt, dobj, TwoTrack(final_steps=4, max_stage_iters=40),
            w0=w0, clock=SimulatedClock(), eval_data=(ds.X, ds.y))
    f = np.asarray(tr.column("f_full"))
    assert np.isfinite(f).all() and f[-1] < f[0]
    windows = [p.window for p in tr.points]
    assert windows == sorted(windows)           # monotone expansion
    assert tr.meta["host_transfers"] <= tr.meta["stages"]


def test_newton_cg_subsample_fraction_tracks_lane_counts():
    """At hessian_fraction < 1 the HostWindows subsample must use R * m_h
    valid rows per lane (the single-host R * n semantics), drawn entirely
    from the lane's valid prefix — never R * capacity, never padding."""
    opt = NewtonCG(hessian_fraction=0.5)
    lanes = jnp.arange(3 * 100 * 3, dtype=jnp.float32).reshape(3, 100, 3)
    hw = HostWindows((lanes,), jnp.asarray([40, 100, 0], jnp.int32))
    for t in range(4):
        sub = opt._subsample(hw, jnp.int32(t))
        counts = np.asarray(sub.counts)
        # R * m_h (not R * cap), and an *empty* lane stays empty — no
        # padding row may ever enter the Hessian
        assert counts.tolist() == [20, 50, 0]
        assert sub.fields[0].shape == (3, 50, 3)    # static slice shape
        for h, m in ((0, 40), (1, 100)):
            rows = np.asarray(sub.fields[0][h][: counts[h]])
            valid = np.asarray(lanes[h][:m])
            assert all(any((r == v).all() for v in valid) for r in rows)
    # hessian_fraction=1.0 is the identity on every non-empty lane
    sub = NewtonCG(hessian_fraction=1.0)._subsample(hw, jnp.int32(2))
    assert np.asarray(sub.counts).tolist() == [40, 100, 0]
    np.testing.assert_array_equal(np.asarray(sub.fields[0]),
                                  np.asarray(lanes))


def test_distributed_engine_rejects_variance_policies():
    ds, _, dobj, w0 = small_problem(n=96, d=4)
    X, y = np.asarray(ds.X), np.asarray(ds.y)
    with DistributedDataset([InMemoryShardStore(X, 16),
                             InMemoryShardStore(y, 16)],
                            num_hosts=2) as dd:
        with pytest.raises(NotImplementedError):
            DistributedBetEngine().run(dd, NewtonCG(), dobj,
                                       GradientVariance(), w0=w0)


# ------------------------------------------------------------------ LM path
def test_distributed_lm_splits_loads_across_hosts():
    from repro import configs
    from repro.launch.train import TrainConfig, train_lm

    cfg = configs.reduced(configs.get("qwen3-0.6b"))
    tr = train_lm(cfg, TrainConfig(schedule="bet", inner_steps=2,
                                   final_steps=3, batch_size=4, seq_len=32,
                                   n0=16, corpus_size=64, shard_size=16,
                                   num_hosts=2))
    assert np.isfinite(np.asarray(tr.column("f_window"))).all()
    assert tr.meta["data_plane"]["examples_loaded"] == 64
    per_host = tr.meta["data_plane_hosts"]
    assert [per_host[h]["examples_loaded"] for h in (0, 1)] == [32, 32]
    # the CLI path runs the *distributed* engine: the collective flush and
    # global accounting land in the trace
    recs = tr.meta["host_stage_records"]
    assert recs and all(len(r["hosts"]) == 2 for r in recs)
    assert tr.meta["dist"]["meter"]["examples_loaded"] == 64
    # every lane participates from the first stage — no zero-padding rows
    # ever enter the per-host batch composition (shard clamp to n0 // hosts)
    assert all(min(h["window"] for h in r["hosts"]) >= 1 for r in recs)


def test_distributed_lm_validates_batch_split_and_participation():
    from repro import configs
    from repro.launch.train import TrainConfig, train_lm
    cfg = configs.reduced(configs.get("qwen3-0.6b"))
    with pytest.raises(ValueError):
        train_lm(cfg, TrainConfig(batch_size=5, num_hosts=2))
    with pytest.raises(ValueError, match="non-empty"):
        train_lm(cfg, TrainConfig(batch_size=8, n0=4, num_hosts=8))


def test_min_full_participation_window():
    own = ShardOwnership(num_shards=8, num_hosts=4, shard_size=16,
                         num_examples=128)
    # striped: host 3's first shard is shard 3 -> window 3*16 + 1
    assert own.min_full_participation_window() == 49
    for n in range(own.min_full_participation_window(), 129):
        assert all(own.examples_in_prefix(h, n) >= 1 for h in range(4))


# --------------------------------------------------------------------- mesh
def test_make_host_mesh_validates_model_axis():
    import jax
    with pytest.raises(ValueError, match="data axis would be empty"):
        make_host_mesh(model=len(jax.devices()) + 1)
    with pytest.raises(ValueError):
        make_host_mesh(model=0)


def test_make_hosts_mesh_validates_device_pool():
    import jax
    with pytest.raises(ValueError):
        make_hosts_mesh(0)
    with pytest.raises(RuntimeError, match="xla_force_host_platform"):
        make_hosts_mesh(len(jax.devices()) + 1)


def test_simulated_topology_degrades_without_devices():
    topo = SimulatedTopology(4)
    assert topo.num_hosts == 4 and topo.local_hosts == (0, 1, 2, 3)
    assert all(len(topo.devices_for(h)) >= 1 for h in range(4))
    with pytest.raises(ValueError):
        SimulatedTopology(0)


def test_simulated_topology_refuses_shared_accelerator():
    """Off the CPU, hosts sharing a device would hide a wrong placement."""
    class Chip:
        platform = "tpu"
    with pytest.raises(ValueError, match="tpu device each"):
        SimulatedTopology(4, devices=[Chip()])
    assert SimulatedTopology(2, devices=[Chip(), Chip()]).num_hosts == 2


# ------------------------------------------- forced-host-platform subprocess
def test_simulated_hosts_on_forced_device_mesh():
    """The real thing, in miniature: 4 forced CPU devices, a ('hosts',)
    mesh, and the stacked window genuinely sharded one lane per host."""
    code = textwrap.dedent("""
        import os
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=4 "
                                   + os.environ.get("XLA_FLAGS", ""))
        import jax
        import numpy as np
        assert jax.device_count() == 4, jax.devices()
        from repro.core import BETSchedule, FixedSteps, SimulatedClock
        from repro.data import InMemoryShardStore
        from repro.data.synthetic import make_classification
        from repro.dist import (DistributedBetEngine, DistributedDataset,
                                SimulatedTopology, distributed_objective,
                                l2_regularizer)
        from repro.models.linear import init_params, make_example_losses
        from repro.optim import NewtonCG

        ds = make_classification("t", n=256, d=16, seed=0)
        X, y = np.asarray(ds.X), np.asarray(ds.y)
        topo = SimulatedTopology(4)
        assert topo.hosts_mesh() is not None
        dd = DistributedDataset([InMemoryShardStore(X, 16),
                                 InMemoryShardStore(y, 16)], topology=topo)
        dobj = distributed_objective(make_example_losses(),
                                     regularizer=l2_regularizer(1e-3))
        tr = DistributedBetEngine(schedule=BETSchedule(n0=32)).run(
            dd, NewtonCG(hessian_fraction=1.0), dobj,
            FixedSteps(inner_steps=2, final_steps=2), w0=init_params(ds.d),
            clock=SimulatedClock(), eval_data=(ds.X, ds.y))
        buf = dd.stacked[0].buffer
        assert len(buf.sharding.device_set) == 4, buf.sharding
        assert np.isfinite(tr.final().f_full)
        assert [m.examples_loaded for m in dd.host_meters] == [64] * 4
        dd.close()
        print("FORCED_MESH_OK")
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=480)
    assert "FORCED_MESH_OK" in out.stdout, (out.stdout, out.stderr)
