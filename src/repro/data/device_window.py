"""Device-resident expanding window.

The PR 1 engine re-uploaded ``dataset.window(n_t)`` wholesale at every
stage.  ``DeviceWindow`` replaces that with BET's actual contract (§3.3):
one device buffer, preallocated at max capacity and sharded over the mesh's
data axes, grown **in place** by ``dynamic_update_slice`` as shards arrive.
Already-resident examples are never transferred again, and because the
buffer's shape is fixed, kernels that consume a ``MaskedWindow`` (buffer +
valid-length scalar) are traced once and reused across every expansion.

Two views:

  * ``masked(n)``  — fixed-shape ``MaskedWindow`` pytree; consumers index
    ``% n_valid`` (the LM path; retrace-free across stages),
  * ``slice(n)``   — a device-side prefix slice ``buf[:n]`` (the convex
    path, whose objectives reduce over the leading axis and stay bit-exact
    against host-side numpy slicing).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from .shards import DataAccessMeter


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class MaskedWindow:
    """A fixed-capacity token/row buffer with a device-side valid length.

    Passing this (instead of a ``buf[:n_t]`` slice) through jitted stage
    kernels keeps their signatures shape-stable: expansion changes only the
    ``n_valid`` scalar, so cached kernels never re-trace."""
    data: Any                   # (capacity, *item_shape) device array
    n_valid: Any                # () int32 device scalar

    def tree_flatten(self):
        return (self.data, self.n_valid), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def capacity(self) -> int:
        return self.data.shape[0]


def window_rows(data):
    """(rows, n) for either a ``MaskedWindow`` or a plain row array — the
    one adapter consumers need to run unchanged on both data paths."""
    if isinstance(data, MaskedWindow):
        return data.data, data.n_valid
    return data, data.shape[0]


# ----------------------------------------------------- lane-aware adapters
# Every stage view the engine hands out — a plain array / (X, y) tuple, a
# MaskedWindow, or the multi-host HostWindows — is "lanes of masked rows":
# one lane for the single-host paths, one per host distributed.  The
# adapters below lift any view to that common form once, so consumers
# (LM batch rotation, measurement probes, the distributed objective, the
# Newton-CG Hessian subsample, the elastic lane-rebuild checks) each have
# exactly one lane-aware implementation instead of scattered
# ``isinstance(data, HostWindows)`` branches.

def as_host_windows(data) -> "HostWindows":
    """Lift any stage view to the stacked per-lane form.

    ``HostWindows`` passes through; a ``MaskedWindow``, a plain row array,
    or a tuple/list of per-field arrays becomes a single fully-valid lane.
    Safe under jit: the lift only adds a leading length-1 axis."""
    if isinstance(data, HostWindows):
        return data
    if isinstance(data, MaskedWindow):
        return HostWindows(
            (data.data[None],),
            jnp.reshape(jnp.asarray(data.n_valid, jnp.int32), (1,)))
    fields = tuple(data) if isinstance(data, (tuple, list)) else (data,)
    count = jnp.asarray([fields[0].shape[0]], jnp.int32)
    return HostWindows(tuple(f[None] for f in fields), count)


def rotation_rows(data, batch_size: int, t):
    """The inner step's global mini-batch: each lane contributes
    ``batch_size // num_lanes`` rows rotating through *its own* valid
    prefix (sequential epochs over resident data — no random disk access),
    concatenated in lane order.  On a single lane this is exactly the
    classic ``(arange(B) + t*B) % n`` rotation."""
    hw = as_host_windows(data)
    per = batch_size // hw.num_hosts

    def one(rows, m):
        idx = (jnp.arange(per) + t * per) % m
        return jnp.take(rows, idx, axis=0)

    picked = jax.vmap(one)(hw.fields[0], hw.counts)     # (lanes, per, ...)
    return picked.reshape((-1,) + picked.shape[2:])


def probe_rows(data, rows: int):
    """A deterministic ``rows``-row measurement probe: an equal per-lane
    share of each lane's valid prefix (wrapping when a lane is smaller),
    concatenated and clipped to ``rows``.

    Precondition (shared with ``rotation_rows``): every lane is non-empty —
    a traced count cannot raise here, so callers keep windows at or above
    ``ShardOwnership.min_full_participation_window()``."""
    hw = as_host_windows(data)
    per = -(-rows // hw.num_hosts)

    def one(lane, m):
        return jnp.take(lane, jnp.arange(per) % m, axis=0)

    picked = jax.vmap(one)(hw.fields[0], hw.counts)
    return picked.reshape((-1,) + picked.shape[2:])[:rows]


def rolling_subwindow(data, fraction: float, t):
    """Type-preserving rolling contiguous sub-window of any stage view —
    the Newton-CG Hessian subsample (decorrelates Hessian error across
    iterations without re-loading anything; BET's no-resampling property
    concerns *data access*, not in-memory slicing).

    A stacked multi-host window subsamples per *lane* — tree-mapping over a
    ``HostWindows`` would slice the hosts axis instead of the example axis.
    The slice is a static ``fraction * capacity`` rows (shapes must not
    depend on traced values) but the *valid count* is ``fraction * m_h``
    per lane, so the effective fraction matches the single-host
    ``fraction * n`` semantics at every stage; the rolling offset stays
    inside both the valid prefix and the buffer, so padding never enters
    the Hessian.  (At ``fraction=1.0`` both layouts reduce to the
    identity, which is what the parity runs use.)"""
    if isinstance(data, HostWindows):
        k = max(1, int(round(fraction * data.capacity)))

        def lane_span(m):
            # floor of 1 only for non-empty lanes: an empty lane (its
            # first owned shard beyond the window) must contribute 0
            # rows, not a padding row
            k_eff = jnp.clip(jnp.round(fraction * m),
                             jnp.minimum(m, 1), m).astype(jnp.int32)
            lim = jnp.minimum(m - k_eff, data.capacity - k)
            off = jnp.mod(t * jnp.maximum(1, k_eff),
                          jnp.maximum(1, lim + 1))
            return off, k_eff

        def take_lane(lane, m):
            off, _ = lane_span(m)
            return jax.lax.dynamic_slice_in_dim(lane, off, k, axis=0)

        fields = tuple(
            jax.vmap(take_lane)(f, data.counts) for f in data.fields)
        counts = jax.vmap(lambda m: lane_span(m)[1])(data.counts)
        return HostWindows(fields, counts)

    def take(x):
        n = x.shape[0]
        k = max(1, int(round(fraction * n)))
        n_off = max(1, n - k + 1)
        off = jnp.mod(t * jnp.int32(max(1, k)), n_off)
        return jax.lax.dynamic_slice_in_dim(x, off, k, axis=0)
    return jax.tree_util.tree_map(take, data)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class HostWindows:
    """Stacked per-host masked windows — the SPMD view of the distributed
    expanding window (dist/runtime.py).

    ``fields`` is one ``(num_hosts, capacity, *item)`` array per data field
    (the convex path's X and y, the LM path's tokens); lane ``h`` holds host
    ``h``'s *owned* examples in its local, prefix-nested order.  ``counts``
    is the ``(num_hosts,)`` int32 vector of per-host valid lengths — hosts
    may disagree because shard-granularity padding differs per lane, which is
    why every consumer reduces through a mask (dist/collectives.py) instead
    of slicing.  Like ``MaskedWindow``, expansion changes only ``counts``,
    so jitted stage kernels never re-trace across stages."""
    fields: tuple
    counts: Any                 # (num_hosts,) int32

    def tree_flatten(self):
        return ((tuple(self.fields), self.counts), None)

    @classmethod
    def tree_unflatten(cls, aux, children):
        fields, counts = children
        return cls(tuple(fields), counts)

    @property
    def num_hosts(self) -> int:
        return self.fields[0].shape[0]

    @property
    def capacity(self) -> int:
        return self.fields[0].shape[1]


# ------------------------------------------------------- in-place grow kernel
_APPEND_CACHE: dict[tuple, Callable] = {}


def _append_kernel(buf_shape, rows_shape, dtype, sharding, *,
                   lane: bool = False) -> Callable:
    """Jitted ``dynamic_update_slice`` append, cached per (buffer shape,
    rows shape).  The plane coalesces each expansion into one append, so
    the cache holds one entry per distinct grow size — bounded by the
    stage count, and shared across runs on the same schedule.

    ``lane=True`` is the multi-host variant: the buffer carries a leading
    hosts axis and rows land in lane ``host`` at ``offset``."""
    key = ("lane" if lane else "row", buf_shape, rows_shape, str(dtype),
           sharding)
    if key in _APPEND_CACHE:
        return _APPEND_CACHE[key]

    if lane:
        def append(buf, rows, host, offset):
            start = (host, offset) + (jnp.int32(0),) * (buf.ndim - 2)
            return jax.lax.dynamic_update_slice(buf, rows[None], start)
    else:
        def append(buf, rows, offset):
            start = (offset,) + (jnp.int32(0),) * (buf.ndim - 1)
            return jax.lax.dynamic_update_slice(buf, rows, start)

    kw = {"out_shardings": sharding} if sharding is not None else {}
    _APPEND_CACHE[key] = jax.jit(append, donate_argnums=(0,), **kw)
    return _APPEND_CACHE[key]


@dataclasses.dataclass
class DeviceWindow:
    """Preallocated expanding window resident on the mesh.

    ``sharding`` (a ``jax.sharding.NamedSharding`` over the data axes)
    places the buffer; appends upload only the new rows and land them with
    ``dynamic_update_slice``, so growing never re-uploads resident data.
    ``growth`` mirrors the stage schedule and is validated like
    ``BETSchedule.growth`` — a factor <= 1 would never fill the window.

    View lifetime: an ``append`` donates the previous buffer and lands the
    rows in place, invalidating views handed out earlier.  Take
    ``masked()``/``slice()`` views *after* the stage's residency is
    settled and drop them before the next expansion — the engine's
    acquire-then-view stage setup follows this order."""
    capacity: int
    item_shape: tuple
    dtype: Any
    growth: float = 2.0
    sharding: Any = None
    meter: DataAccessMeter | None = None
    # multi-field planes (X, y) append the same example range to several
    # windows; only one of them should count *examples* uploaded (bytes are
    # genuinely per-field and always counted)
    meter_examples: bool = True

    def __post_init__(self):
        if self.capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {self.capacity}")
        if not self.growth > 1.0:
            raise ValueError(
                f"DeviceWindow.growth must be > 1, got {self.growth}: the "
                "window would never expand to its full capacity")
        self.item_shape = tuple(self.item_shape)
        shape = (self.capacity,) + self.item_shape
        if self.sharding is not None:
            # allocate straight into the sharded layout — a host zeros +
            # device_put would commit the full unsharded buffer to one
            # device first, double the peak footprint at capacity scale
            self._buf = jax.jit(lambda: jnp.zeros(shape, self.dtype),
                                out_shardings=self.sharding)()
        else:
            self._buf = jnp.zeros(shape, self.dtype)
        self._n = 0
        self._n_dev = jnp.int32(0)

    # ---------------------------------------------------------------- state
    @property
    def n_valid(self) -> int:
        return self._n

    @property
    def buffer(self):
        return self._buf

    @property
    def full(self) -> bool:
        return self._n >= self.capacity

    def next_size(self) -> int:
        """The schedule's next window: n_{t+1} = min(cap, ceil(g * n_t))."""
        return min(self.capacity, int(math.ceil(max(1, self._n) * self.growth)))

    # --------------------------------------------------------------- updates
    def append(self, rows: np.ndarray) -> int:
        """Upload ``rows`` and land them in place after the resident prefix.
        Returns the new valid length."""
        rows = np.asarray(rows)
        if rows.shape[1:] != self.item_shape:
            raise ValueError(
                f"rows shape {rows.shape[1:]} != item shape {self.item_shape}")
        k = int(rows.shape[0])
        if self._n + k > self.capacity:
            raise ValueError(
                f"append of {k} rows overflows window "
                f"({self._n}/{self.capacity} resident)")
        kernel = _append_kernel(self._buf.shape, rows.shape, self._buf.dtype,
                                self.sharding)
        self._buf = kernel(self._buf, np.asarray(rows, self._buf.dtype),
                           jnp.int32(self._n))
        if self.meter is not None:
            self.meter.record_upload(nbytes=rows.nbytes,
                                     examples=k if self.meter_examples else 0)
        self._n += k
        self._n_dev = jnp.int32(self._n)
        return self._n

    def append_staged(self, rows) -> int:
        """Land rows that are *already on device* (the tiered corpus's
        double-buffered staging path: ``jax.device_put`` ran on the staging
        thread while the previous stage computed).  Same in-place
        ``dynamic_update_slice`` landing as :meth:`append`, but no host
        array conversion and **no upload metering** — the commit path
        meters the transfer itself, on the driver thread, so discarded
        staged buffers are never counted."""
        if tuple(rows.shape[1:]) != self.item_shape:
            raise ValueError(
                f"rows shape {tuple(rows.shape[1:])} != item shape "
                f"{self.item_shape}")
        k = int(rows.shape[0])
        if self._n + k > self.capacity:
            raise ValueError(
                f"append of {k} staged rows overflows window "
                f"({self._n}/{self.capacity} resident)")
        kernel = _append_kernel(self._buf.shape, rows.shape, self._buf.dtype,
                                self.sharding)
        self._buf = kernel(self._buf, rows, jnp.int32(self._n))
        self._n += k
        self._n_dev = jnp.int32(self._n)
        return self._n

    # ---------------------------------------------------------------- cursor
    def cursor(self) -> dict:
        """Checkpointable residency bookkeeping: together with the fixed
        permutation, ``n_valid`` fully determines the window's contents."""
        return {"n_valid": self._n}

    def restore_cursor(self, cursor: dict) -> None:
        """Restore the valid-length bookkeeping from a checkpoint.  Pure
        cursor state: the caller is responsible for re-landing the first
        ``n_valid`` examples beneath it (a resumed plane replays
        ``ensure_resident``); restoring beyond what will be re-landed would
        expose stale buffer rows."""
        n = int(cursor["n_valid"])
        if not 0 <= n <= self.capacity:
            raise ValueError(
                f"cursor n_valid={n} outside window capacity {self.capacity}")
        self._n = n
        self._n_dev = jnp.int32(n)

    # ----------------------------------------------------------------- views
    def masked(self, n: int | None = None) -> MaskedWindow:
        """Fixed-shape view exposing the first ``n`` (default: all resident)
        examples through the valid-length mask."""
        if n is None:
            return MaskedWindow(self._buf, self._n_dev)
        if n > self._n:
            raise ValueError(f"window {n} exceeds resident prefix {self._n}")
        return MaskedWindow(self._buf, jnp.int32(n))

    def slice(self, n: int):
        """Device-side prefix slice (the convex path's (X[:n], y[:n]))."""
        if n > self._n:
            raise ValueError(f"window {n} exceeds resident prefix {self._n}")
        return self._buf[:n]


# --------------------------------------------------- multi-host stacked window
@dataclasses.dataclass
class StackedDeviceWindow:
    """The multi-host DeviceWindow: one ``(num_hosts, capacity, *item)``
    buffer whose lane ``h`` is host ``h``'s expanding window, grown in place
    per lane via ``dynamic_update_slice``.

    With ``sharding = P('hosts', ...)`` over a hosts mesh, lane ``h`` lives
    on host ``h``'s device, so an append from host ``h`` only writes its own
    shard and resident lanes are never re-uploaded.  This is the
    single-process SPMD *simulation* of the runtime: a real multi-process
    deployment allocates only its local lane and the stacked axis exists
    logically through the named mesh axis (dist/collectives.AxisCollectives).

    ``meters`` is an optional per-host ``DataAccessMeter`` sequence — lane
    appends charge the owning host's meter, which is what keeps per-host
    upload accounting separable in the global Thm 4.1 reduction."""
    num_hosts: int
    capacity: int
    item_shape: tuple
    dtype: Any
    growth: float = 2.0
    sharding: Any = None
    meters: Any = None
    meter_examples: bool = True

    def __post_init__(self):
        if self.num_hosts < 1:
            raise ValueError(f"num_hosts must be >= 1, got {self.num_hosts}")
        if self.capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {self.capacity}")
        if not self.growth > 1.0:
            raise ValueError(
                f"StackedDeviceWindow.growth must be > 1, got {self.growth}")
        if self.meters is not None and len(self.meters) != self.num_hosts:
            raise ValueError(f"{len(self.meters)} meters for "
                             f"{self.num_hosts} hosts")
        self.item_shape = tuple(self.item_shape)
        shape = (self.num_hosts, self.capacity) + self.item_shape
        if self.sharding is not None:
            self._buf = jax.jit(lambda: jnp.zeros(shape, self.dtype),
                                out_shardings=self.sharding)()
        else:
            self._buf = jnp.zeros(shape, self.dtype)
        self._n = [0] * self.num_hosts

    @property
    def buffer(self):
        return self._buf

    @property
    def counts(self) -> np.ndarray:
        return np.asarray(self._n, np.int32)

    def lane_valid(self, host: int) -> int:
        return self._n[host]

    def next_size(self, host: int) -> int:
        """Lane ``host``'s next scheduled window size."""
        return min(self.capacity,
                   int(math.ceil(max(1, self._n[host]) * self.growth)))

    def append(self, host: int, rows: np.ndarray) -> int:
        """Upload ``rows`` into lane ``host`` after its resident prefix."""
        if not 0 <= host < self.num_hosts:
            raise IndexError(host)
        rows = np.asarray(rows)
        if rows.shape[1:] != self.item_shape:
            raise ValueError(
                f"rows shape {rows.shape[1:]} != item shape {self.item_shape}")
        k = int(rows.shape[0])
        if self._n[host] + k > self.capacity:
            raise ValueError(
                f"append of {k} rows overflows lane {host} "
                f"({self._n[host]}/{self.capacity} resident)")
        kernel = _append_kernel(self._buf.shape, rows.shape,
                                self._buf.dtype, self.sharding, lane=True)
        self._buf = kernel(self._buf, np.asarray(rows, self._buf.dtype),
                           jnp.int32(host), jnp.int32(self._n[host]))
        if self.meters is not None:
            self.meters[host].record_upload(
                nbytes=rows.nbytes, examples=k if self.meter_examples else 0)
        self._n[host] += k
        return self._n[host]

    def reset_lane(self, host: int) -> None:
        """Forget lane ``host``'s resident prefix — the host-loss recovery
        primitive.  A real host failure destroys the lane's device memory,
        so the simulation zeroes the lane as well as its cursor: the
        replacement host must genuinely re-read the lane's owned slice from
        storage, and tests/benchmarks can prove it did."""
        if not 0 <= host < self.num_hosts:
            raise IndexError(host)
        self._buf = self._buf.at[host].set(jnp.zeros((), self._buf.dtype))
        self._n[host] = 0

    def cursor(self) -> dict:
        """Checkpointable per-lane residency bookkeeping."""
        return {"counts": [int(n) for n in self._n]}

    def restore_cursor(self, cursor: dict) -> None:
        """Restore per-lane valid lengths (same contract as
        ``DeviceWindow.restore_cursor``: the caller re-lands the data)."""
        counts = [int(c) for c in cursor["counts"]]
        if len(counts) != self.num_hosts:
            raise ValueError(
                f"cursor has {len(counts)} lanes, window {self.num_hosts}")
        if any(not 0 <= c <= self.capacity for c in counts):
            raise ValueError(
                f"cursor counts {counts} outside capacity {self.capacity}")
        self._n = counts

    def lane(self, host: int) -> "WindowLane":
        return WindowLane(self, host)


class WindowLane:
    """One host's view of a ``StackedDeviceWindow``, quacking like a
    ``DeviceWindow`` for the streaming plane's residency bookkeeping — this
    is what lets ``DistributedDataset`` drive one ``StreamingDataset`` per
    host while all lanes share the single stacked SPMD buffer."""

    def __init__(self, stacked: StackedDeviceWindow, host: int):
        if not 0 <= host < stacked.num_hosts:
            raise IndexError(host)
        self._stacked = stacked
        self.host = host

    @property
    def n_valid(self) -> int:
        return self._stacked.lane_valid(self.host)

    @property
    def buffer(self):
        return self._stacked.buffer

    def next_size(self) -> int:
        return self._stacked.next_size(self.host)

    def append(self, rows: np.ndarray) -> int:
        return self._stacked.append(self.host, rows)
