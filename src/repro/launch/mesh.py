"""Cluster topology + mesh construction (one path for every mesh).

Defined as functions (never module-level constants) so importing this module
never touches jax device state - jax locks the device count on first init,
and only dryrun.py sets the 512-placeholder XLA flag.

`Topology` is the first-class description of the physical shuffle fabric:
`racks` super-nodes of `servers_per_rack` hosts each, with server k living
in rack ``k // servers_per_rack`` (contiguous blocks).  `Topology.flat(K)`
is the degenerate one-server-per-rack form - every level-dependent decision
in the shuffle stack (plan compilation, fused exchange, load accounting)
flows from a `Topology` and reduces to today's flat K-server behavior on
`Topology.flat(K)`.

Every mesh in the repo is built through `make_mesh` below: the coded-Shuffle
meshes (`make_servers_mesh`, `make_racks_mesh`) use the device-prefix form
(a host with 8 forced CPU devices can still run a K=4 plan), the
training/serving meshes (`make_production_mesh`, `make_local_mesh`) the
all-devices form.
"""
from __future__ import annotations

import dataclasses

import numpy as np

# jax is imported inside the mesh-building functions, not at module scope:
# `Topology` is consumed by the numpy-only core (plan compiler, loads), and
# importing it must stay free of jax side effects.


@dataclasses.dataclass(frozen=True)
class Topology:
    """Two-level cluster shape: `racks` x `servers_per_rack` servers.

    Server k lives in rack ``k // servers_per_rack``; rack rho owns the
    contiguous server block ``[rho * servers_per_rack,
    (rho + 1) * servers_per_rack)``.  Intra-rack links are assumed cheap
    relative to inter-rack links, so the hierarchical coded Shuffle codes
    across racks and exchanges plainly within them
    (`core.shuffle_plan.compile_hierarchical`).
    """

    racks: int
    servers_per_rack: int

    def __post_init__(self):
        if self.racks < 1 or self.servers_per_rack < 1:
            raise ValueError(
                f"need racks >= 1 and servers_per_rack >= 1, got "
                f"racks={self.racks}, servers_per_rack={self.servers_per_rack}")

    @classmethod
    def flat(cls, K: int) -> "Topology":
        """The degenerate flat topology: every server its own rack."""
        return cls(racks=K, servers_per_rack=1)

    @property
    def K(self) -> int:
        """Total server count."""
        return self.racks * self.servers_per_rack

    @property
    def is_flat(self) -> bool:
        return self.servers_per_rack == 1

    def check_K(self, K: int) -> None:
        if self.K != K:
            raise ValueError(
                f"topology has {self.racks} x {self.servers_per_rack} = "
                f"{self.K} servers but the allocation expects K={K}")

    def rack_of(self) -> np.ndarray:
        """[K] int32: server index -> rack index."""
        return (np.arange(self.K, dtype=np.int32)
                // np.int32(self.servers_per_rack))

    def servers_in(self, rack: int) -> np.ndarray:
        """[S] int32: the servers of one rack (ascending)."""
        S = self.servers_per_rack
        return np.arange(rack * S, (rack + 1) * S, dtype=np.int32)

    def leader_of(self) -> np.ndarray:
        """[R] int32: the leader (lowest-index server) of each rack."""
        return (np.arange(self.racks, dtype=np.int32)
                * np.int32(self.servers_per_rack))


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...], *,
              prefix: bool = False):
    """The one mesh-construction path (every make_* helper routes here).

    `prefix=False` builds a mesh over *all* devices via `jax.make_mesh`
    with Auto axis types.

    `prefix=True` builds the Mesh explicitly from a device *prefix* of
    ``prod(shape)`` devices - `jax.make_mesh` wants the axis sizes to
    consume all devices, but the coded-Shuffle path maps one server per
    device and must run on hosts with spare forced CPU devices.
    """
    import jax

    if prefix:
        from jax.sharding import Mesh

        need = int(np.prod(shape))
        devs = jax.devices()
        if len(devs) < need:
            raise ValueError(
                f"need {need} devices for mesh shape {shape} but only "
                f"{len(devs)} devices exist; force host devices with "
                f"XLA_FLAGS=--xla_force_host_platform_device_count={need}")
        return Mesh(np.asarray(devs[:need]).reshape(shape), axes)
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_mesh_auto(shape, axes):
    """Back-compat alias of the all-devices form of `make_mesh`."""
    return make_mesh(tuple(shape), tuple(axes))


def make_servers_mesh(K: int):
    """('servers',) mesh over the first K devices (devices = servers).

    The flat coded-Shuffle fused path maps one Shuffle server per device.
    """
    return make_mesh((K,), ("servers",), prefix=True)


def make_racks_mesh(topology: Topology):
    """('racks', 'servers') mesh over the first R x S devices.

    Device (rho, s) is server ``rho * S + s`` - the same contiguous-block
    rule as `Topology.rack_of`, so plan server indices and mesh coordinates
    agree by construction. The hierarchical fused exchange runs its coded
    XOR all_gather on the 'racks' axis and its plain gather/scatter on the
    'servers' axis.
    """
    return make_mesh((topology.racks, topology.servers_per_rack),
                     ("racks", "servers"), prefix=True)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single pod (256 chips) or 2x16x16 (2 pods, 512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(data: int = 1, model: int = 1):
    """Small mesh over whatever devices exist (tests / examples)."""
    return make_mesh((data, model), ("data", "model"))

