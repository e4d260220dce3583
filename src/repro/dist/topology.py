"""Host topology — who the hosts are and which devices each one drives.

The paper's distributed claim (§3.3, Fig. 5) is about *hosts*: each worker
keeps its resident data and streams in only its share of every expansion.
JAX exposes real hosts as processes (``jax.process_index/count``), which CI
cannot spawn — so the runtime is written against a ``HostTopology`` protocol
with two implementations:

  * ``ProcessTopology`` — the real thing: one JAX process per host
    (``jax.distributed.initialize`` on a pod); each process drives only its
    own host and sees only its local devices.

  * ``SimulatedTopology`` — N *logical* hosts in one process.  Run under

        XLA_FLAGS=--xla_force_host_platform_device_count=N

    and each logical host gets its own CPU device, the hosts mesh is real,
    and the stacked window (data/device_window.StackedDeviceWindow) is
    genuinely sharded one lane per host — the whole runtime is then testable
    on CPU CI.  With fewer CPU devices than hosts (the plain single-device
    test environment) the logical hosts share devices and the mesh degrades
    to ``None``; all ownership/collective *math* is unchanged, only
    placement is.  On an accelerator that sharing would hide a wrong
    placement, so there fewer devices than hosts is an error.

Everything here must be import-safe before device state matters: topologies
query ``jax.devices()`` lazily, at construction."""
from __future__ import annotations

import dataclasses

import jax

from ..launch.mesh import make_hosts_mesh


def force_host_device_flag(num_hosts: int) -> str:
    """The XLA flag that materializes ``num_hosts`` CPU devices.  Must be in
    ``XLA_FLAGS`` *before* jax initializes its backends — set it in the
    environment of a fresh process, never mid-session."""
    return f"--xla_force_host_platform_device_count={num_hosts}"


class HostTopology:
    """Protocol: the set of hosts and the devices backing each one."""

    @property
    def num_hosts(self) -> int:
        raise NotImplementedError

    @property
    def local_hosts(self) -> tuple:
        """Hosts this process drives: all of them when simulated, exactly
        one under a real multi-process runtime."""
        raise NotImplementedError

    def devices_for(self, host: int) -> tuple:
        raise NotImplementedError

    def hosts_mesh(self):
        """A 1-D ``('hosts',)`` mesh with one representative device per
        host, or ``None`` when the device pool cannot express one."""
        return None

    def window_sharding(self, ndim: int):
        """``NamedSharding`` placing a ``(num_hosts, ...)``-leading stacked
        buffer one lane per host, or ``None`` without a hosts mesh."""
        mesh = self.hosts_mesh()
        if mesh is None:
            return None
        from jax.sharding import NamedSharding, PartitionSpec as P
        return NamedSharding(mesh, P("hosts", *([None] * (ndim - 1))))

    def describe(self) -> dict:
        return {"kind": type(self).__name__, "num_hosts": self.num_hosts,
                "local_hosts": list(self.local_hosts),
                "devices": {h: [str(d) for d in self.devices_for(h)]
                            for h in self.local_hosts}}


@dataclasses.dataclass
class SimulatedTopology(HostTopology):
    """N logical hosts over this process's device pool.

    With ``len(devices) >= num_hosts`` the pool is split into contiguous
    per-host groups (forced-host-platform CI, or one logical host per
    accelerator).  With fewer CPU devices, hosts share devices cyclically
    and no hosts mesh exists — placement degrades, semantics do not; with
    fewer accelerator devices the topology refuses to build."""

    def __init__(self, num_hosts: int, *, devices=None):
        if num_hosts < 1:
            raise ValueError(f"num_hosts must be >= 1, got {num_hosts}")
        self._num_hosts = int(num_hosts)
        self._devices = tuple(devices) if devices is not None \
            else tuple(jax.devices())
        platform = self._devices[0].platform
        if len(self._devices) < self._num_hosts and platform != "cpu":
            raise ValueError(
                f"{self._num_hosts} simulated hosts need a {platform} "
                f"device each, but only {len(self._devices)} are visible; "
                f"sharing devices between hosts is a CPU-test fallback")

    @property
    def num_hosts(self) -> int:
        return self._num_hosts

    @property
    def local_hosts(self) -> tuple:
        return tuple(range(self._num_hosts))

    def devices_for(self, host: int) -> tuple:
        if not 0 <= host < self._num_hosts:
            raise IndexError(host)
        n_dev = len(self._devices)
        if n_dev >= self._num_hosts:
            per = n_dev // self._num_hosts
            return self._devices[host * per: (host + 1) * per]
        return (self._devices[host % n_dev],)

    def hosts_mesh(self):
        if len(self._devices) < self._num_hosts:
            return None
        return make_hosts_mesh(
            self._num_hosts,
            devices=[self.devices_for(h)[0] for h in self.local_hosts])


class ProcessTopology(HostTopology):
    """One real JAX process per host.  This process drives only host
    ``jax.process_index()``; remote devices are not addressable from here,
    so ``devices_for`` answers only for the local host."""

    def __init__(self):
        self._index = jax.process_index()
        self._count = jax.process_count()

    @property
    def num_hosts(self) -> int:
        return self._count

    @property
    def local_hosts(self) -> tuple:
        return (self._index,)

    def devices_for(self, host: int) -> tuple:
        if host != self._index:
            raise ValueError(
                f"host {host} is remote; process {self._index} can only "
                f"address its local devices")
        return tuple(jax.local_devices())
