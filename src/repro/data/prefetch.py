"""Background shard prefetching — the paper's load/compute overlap, for real.

§3.3: while the optimizer runs stage t on the resident window, the shards
for stage t+1 stream in concurrently.  ``Prefetcher`` realizes that with a
small thread pool: the data plane *schedules* the next stage's shards when a
stage begins, device computation proceeds, and when the expansion finally
*takes* a shard the load has (ideally) already finished.  The demand-side
wait is what the ``DataAccessMeter`` records as ``blocked_time_s`` — zero
blocked time means the loads were fully hidden.

A prefetcher serves one or more *field* stores in lockstep (e.g. the convex
path's X and y): shard i is one unit covering the same example range in
every store, so residency bookkeeping stays scalar.

Failure contract: a background load that raises does **not** stay hidden
until its own ``take`` — every subsequent ``schedule``/``take`` call first
sweeps completed futures and re-raises the failure as ``ShardLoadError``
(original exception chained), so the driving thread learns about a dead
storage path at the next stage boundary instead of one expansion later.
"""
from __future__ import annotations

import threading
import time
from concurrent.futures import CancelledError, Future, ThreadPoolExecutor
from typing import Sequence

import numpy as np

# ShardLoadError lives with the stores now (MemmapShardStore raises it for
# corrupt files too); re-exported here because the failure contract above
# is where the name was born
from .shards import DataAccessMeter, ShardLoadError, ShardStore

__all__ = ["Prefetcher", "ShardLoadError"]


class Prefetcher:
    """Asynchronous loader over parallel shard stores.

    ``schedule`` / ``take`` are called from the driving thread only; worker
    threads just execute loads.  Taking an unscheduled shard degrades to a
    synchronous (fully blocked) demand load, so correctness never depends on
    the prefetch horizon.

    ``max_workers`` defaults to 1 — the paper's sequential-loading channel
    (§4.2's rate ``a``), and what keeps ``DataAccessMeter.overlap_fraction``
    honest: with one worker, load time can only hide behind *computation*.
    More workers raise throughput but also let loads hide behind each
    other, inflating the overlap metric with IO-IO parallelism.

    ``close`` is idempotent and safe against a concurrent ``schedule`` (the
    teardown race when an engine thread is still prefetching while the owner
    shuts the plane down): whichever side takes the lock second wins nothing
    — a post-close ``schedule`` is a silent no-op, and only a post-close
    ``take`` raises, because dropping a demand load is a correctness error
    while dropping a prefetch hint is not.

    ``max_inflight`` bounds how many scheduled loads may hold host RAM at
    once (loaded-but-not-taken shards are the peak): excess hints queue in
    an ordered backlog and are submitted as earlier loads are *taken*, so a
    large next-stage schedule exerts backpressure instead of materializing
    the whole expansion in memory.  Demand loads (``take`` of a backlogged
    or unscheduled shard) always run immediately — the bound throttles
    hints, never correctness.  ``None`` (default) keeps the historical
    unbounded behavior."""

    def __init__(self, stores: Sequence[ShardStore],
                 meter: DataAccessMeter | None = None, *, max_workers: int = 1,
                 max_inflight: int | None = None):
        stores = tuple(stores)
        if not stores:
            raise ValueError("Prefetcher needs at least one store")
        sizes = {(s.num_examples, s.shard_size) for s in stores}
        if len(sizes) != 1:
            raise ValueError(
                f"field stores disagree on (num_examples, shard_size): {sizes}")
        if max_inflight is not None and max_inflight < 1:
            raise ValueError(
                f"max_inflight must be >= 1 (or None for unbounded), "
                f"got {max_inflight}")
        self.stores = stores
        self.meter = meter
        self.max_inflight = max_inflight
        self._pool = ThreadPoolExecutor(max_workers=max_workers,
                                        thread_name_prefix="bet-prefetch")
        self._pending: dict[int, Future] = {}
        self._backlog: list[int] = []       # scheduled, awaiting a slot
        self._lock = threading.Lock()
        self._closed = False
        # observability (repro.obs.metrics.attach_prefetcher): when wired,
        # schedule/load/land/cancel each emit one event; ``prefetch.loaded``
        # fires on the worker thread, interleaved with the driving thread's
        # events in recorder ``seq`` order
        self.recorder = None
        self.recorder_tags: dict = {}

    def _obs(self, name: str, **fields) -> None:
        rec = self.recorder
        if rec is not None:
            rec.instant(name, tags=self.recorder_tags or None, **fields)

    def _obs_depth(self, inflight: int, backlog: int) -> None:
        rec = self.recorder
        if rec is not None:
            rec.counter("prefetch.depth", tags=self.recorder_tags or None,
                        inflight=inflight, backlog=backlog)

    def _pump_locked(self) -> list[int]:
        """Submit backlogged hints while in-flight slots are free (caller
        holds the lock).  Returns the ids submitted, for emission."""
        started = []
        while self._backlog and (
                self.max_inflight is None
                or len(self._pending) < self.max_inflight):
            i = self._backlog.pop(0)
            self._pending[i] = self._pool.submit(self._timed_load, i)
            started.append(i)
        return started

    # ------------------------------------------------------------------ api
    def schedule(self, shard_ids) -> None:
        """Begin loading shards in the background (idempotent per shard).
        Beyond ``max_inflight``, hints queue in the backlog and start as
        earlier loads are taken.  No-op after ``close``; raises
        ``ShardLoadError`` eagerly if any previously scheduled load has
        already failed."""
        with self._lock:
            if self._closed:
                return
            self._sweep_failures_locked()
            new_ids = [i for i in shard_ids
                       if i not in self._pending and i not in self._backlog]
            self._backlog.extend(new_ids)
        # emit outside the lock, and before the loads start: a worker's
        # prefetch.loaded must follow its shard's prefetch.scheduled even
        # when this thread is descheduled between submit and emit
        for i in new_ids:
            self._obs("prefetch.scheduled", shard=int(i))
        with self._lock:
            if self._closed:
                return
            self._pump_locked()
            inflight, backlog = len(self._pending), len(self._backlog)
        if new_ids:
            self._obs_depth(inflight, backlog)

    def cancel(self, shard_ids) -> list[int]:
        """Drop scheduled loads whose shards no longer belong here (elastic
        ownership migration, lane rebuild).  Queued futures are cancelled;
        loads already running cannot be interrupted, so their futures are
        *dropped* instead — the result (possibly read through a stale
        local→global mapping) is discarded, never landed at a window offset
        it no longer corresponds to, and never metered.  Returns the local
        shard ids that were actually pending.  No-op after ``close``."""
        with self._lock:
            if self._closed:
                return []
            dropped = []
            for i in list(shard_ids):
                fut = self._pending.pop(i, None)
                if fut is not None:
                    fut.cancel()
                    dropped.append(i)
                elif i in self._backlog:
                    self._backlog.remove(i)
                    dropped.append(i)
            self._pump_locked()
            inflight, backlog = len(self._pending), len(self._backlog)
        for i in dropped:
            self._obs("prefetch.cancelled", shard=int(i))
        if dropped:
            self._obs_depth(inflight, backlog)
        return dropped

    def scheduled(self) -> list[int]:
        """All shards currently scheduled (submitted or backlogged, not yet
        taken)."""
        with self._lock:
            return sorted(set(self._pending) | set(self._backlog))

    def unfinished(self) -> list[int]:
        """Scheduled shards whose loads have not completed yet — the
        straggler detector's backlog measure at a stage flush."""
        with self._lock:
            return sorted({i for i, fut in self._pending.items()
                           if not fut.done()} | set(self._backlog))

    def inflight(self) -> int:
        """Submitted-but-not-taken loads — the host-RAM bound
        ``max_inflight`` enforces (loaded shards hold their arrays until
        taken)."""
        with self._lock:
            return len(self._pending)

    def take(self, shard: int, *, hidden: bool = False
             ) -> tuple[np.ndarray, ...]:
        """Block until ``shard`` is loaded and return one array per store.
        Taking frees an in-flight slot, so the next backlogged hint starts
        here — backpressure releases exactly as fast as the consumer
        drains.

        ``hidden=True`` records the wait as fully overlapped
        (``blocked_s=0``): the tiered corpus consumes shards on a
        background staging thread whose blocking is by construction
        concurrent with driver compute, and charging it as demand-side
        blocked time would misreport the §3.3 overlap."""
        with self._lock:
            self._check_open()
            self._sweep_failures_locked()
            fut = self._pending.pop(shard, None)
            prefetched = fut is not None
            if fut is None:
                # a demand load bypasses the bound; drop a backlogged hint
                # for the same shard so it cannot double-load later
                if shard in self._backlog:
                    self._backlog.remove(shard)
                fut = self._pool.submit(self._timed_load, shard)
            started = self._pump_locked()
            inflight, backlog = len(self._pending), len(self._backlog)
        if started:
            self._obs_depth(inflight, backlog)
        t0 = time.perf_counter()
        try:
            arrays, duration = fut.result()
        except CancelledError:
            # a close() racing this take cancelled the queued load —
            # CancelledError is a BaseException, so name the race instead
            # of letting it escape raw (the documented post-close contract)
            raise RuntimeError(
                f"Prefetcher closed while shard {shard} was in flight") \
                from None
        except Exception as exc:
            raise ShardLoadError(shard, exc) from exc
        blocked = 0.0 if hidden else time.perf_counter() - t0
        if self.meter is not None:
            self.meter.record_load(
                nbytes=sum(a.nbytes for a in arrays),
                examples=self.stores[0].examples_in(shard),
                duration_s=duration, blocked_s=blocked, prefetched=prefetched)
        self._obs("prefetch.landed", shard=int(shard),
                  prefetched=prefetched, blocked_s=blocked,
                  duration_s=duration)
        return arrays

    def close(self) -> None:
        with self._lock:
            if self._closed:
                return
            self._closed = True
            pending = dict(self._pending)
            self._pending.clear()
            self._backlog.clear()
        # shut down outside the lock: workers may take a while to drain and
        # a racing schedule()/take() must not block on them
        for fut in pending.values():
            fut.cancel()
        self._pool.shutdown(wait=True, cancel_futures=True)

    def __enter__(self) -> "Prefetcher":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # ------------------------------------------------------------- internals
    def _check_open(self) -> None:
        if self._closed:
            raise RuntimeError("Prefetcher is closed")

    def _sweep_failures_locked(self) -> None:
        """Surface any already-failed background load now (caller holds the
        lock).  The failed future is dropped so a retry can be rescheduled."""
        for i, fut in list(self._pending.items()):
            if fut.done() and not fut.cancelled():
                exc = fut.exception()
                if exc is not None:
                    del self._pending[i]
                    raise ShardLoadError(i, exc) from exc

    def _timed_load(self, shard: int):
        t0 = time.perf_counter()
        arrays = tuple(s.load(shard) for s in self.stores)
        duration = time.perf_counter() - t0
        # worker-thread emission: the event-ordering tests pin that this
        # lands after the shard's prefetch.scheduled and before its landed
        self._obs("prefetch.loaded", shard=int(shard), duration_s=duration)
        return arrays, duration
