"""Device topology: the hardware half of a (strategy, topology) pairing.

The paper's core argument is that the right parallelization strategy is a
function of the *cluster*, not just the model: island size (NVLink node /
ICI pod), fabric bandwidths, and chip count all move the optimum.  A
``Topology`` names those facts once so that

  * ``Strategy.to_plan``  builds the SPMD mesh from it (no hard-coded
    ``(16, 16)`` shapes), and
  * ``Strategy.to_cost_strategy`` / ``planner.search`` charge collectives
    for exactly the group sizes that mesh will produce.

``build_mesh`` can also build an ``AbstractMesh`` (no devices needed) so
plans for a 512-chip pod can be *analyzed* on a laptop; only execution
needs the real chips.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

from repro.core import costmodel as cm


@dataclasses.dataclass(frozen=True)
class Topology:
    """A cluster shape + the hardware generation that fills it."""
    name: str
    n_devices: int
    island: int                  # chips per fast island (DGX node / TPU pod)
    hardware: str = "TPUv5e"     # key into costmodel.HARDWARE
    hbm: float = 16e9            # per-chip HBM capacity, bytes
    hw_obj: Optional[cm.Hardware] = None  # explicit profile (e.g. calibrated
    #                              variant) overrides the HARDWARE lookup

    def __post_init__(self):
        assert self.n_devices >= 1 and self.island >= 1
        if self.hw_obj is None:
            assert self.hardware in cm.HARDWARE, (
                f"unknown hardware {self.hardware!r}; "
                f"known: {sorted(cm.HARDWARE)}")

    @property
    def hw(self) -> cm.Hardware:
        if self.hw_obj is not None:
            return self.hw_obj
        return cm.HARDWARE[self.hardware]

    @property
    def n_islands(self) -> int:
        return max(1, self.n_devices // self.island)


# jax ``device_kind`` -> (costmodel.HARDWARE key, per-chip HBM bytes)
DEVICE_PROFILES = {
    "TPU v5 lite": ("TPUv5e", 16e9),
}


def host_topology(hardware: str = "H100", hbm: float = 80e9,
                  n_devices: Optional[int] = None) -> Topology:
    """Whatever devices this process sees, as one fast island.

    On an accelerator the cost-model profile and HBM come from the
    device's ``device_kind`` through ``DEVICE_PROFILES``; a kind missing
    from the table raises rather than planning against another chip.  On
    the CPU, which has no profile of its own, ``hardware``/``hbm`` name
    the generation the planner assumes — a planning assumption, not a
    measurement.
    """
    import jax
    devices = jax.devices()
    n = n_devices or len(devices)
    if devices[0].platform != "cpu":
        kind = devices[0].device_kind
        if kind not in DEVICE_PROFILES:
            raise ValueError(
                f"no cost-model profile for device kind {kind!r}; known: "
                f"{sorted(DEVICE_PROFILES)}")
        hardware, hbm = DEVICE_PROFILES[kind]
    return Topology("host", n, island=n, hardware=hardware, hbm=hbm)


def pod_topology(pods: int = 1, chips_per_pod: int = 256,
                 hardware: str = "TPUv5e", hbm: float = 16e9) -> Topology:
    """The production target: TPU v5e pod(s), DCN-connected above 1 pod."""
    name = "pod" if pods == 1 else f"multipod{pods}"
    return Topology(name, pods * chips_per_pod, island=chips_per_pod,
                    hardware=hardware, hbm=hbm)


def get_topology(name: str, **kw) -> Topology:
    """CLI entry: 'host' | 'pod' | 'multipod' | 'multipod<k>'."""
    if name == "host":
        return host_topology(**kw)
    if name == "pod":
        return pod_topology(pods=1, **kw)
    if name.startswith("multipod"):
        pods = int(name[len("multipod"):] or 2)
        return pod_topology(pods=pods, **kw)
    raise ValueError(f"unknown topology {name!r} "
                     "(expected host | pod | multipod[<k>])")


def build_mesh(topology: Topology, model: int = 1, pods: int = 1,
               pipe: int = 1, expert: int = 1, abstract: bool = False):
    """Mesh for ``topology`` with given model-, pipe- and expert-axis degrees.

    pods > 1 adds a leading 'pod' axis (HSDP: params sharded inside the
    island, replicated across pods).  pipe > 1 adds a 'pipe' axis for
    GPipe stages, placed outermost below 'pod' so stages span the slow
    fabric first (pipeline p2p is the cheapest cross-island traffic —
    the paper's argument for PP at scale).  expert > 1 adds an 'expert'
    axis *factored out of the data axis* (data = dp / expert): batch and
    gradients shard over (data, expert) together, while MoE expert stacks
    shard their E dim over 'expert' only — the dispatch/combine
    all-to-all runs along it.  It sits between 'data' and 'model' so the
    ep-group ranks are as mesh-adjacent as the model axis allows.
    ``abstract=True`` returns an ``AbstractMesh`` — enough for
    PartitionSpec/group-size analysis without any devices attached.
    """
    n = topology.n_devices
    if n % (model * pods * pipe * expert):
        raise ValueError(
            f"mesh ({pods} pods x pipe {pipe} x expert {expert} x model "
            f"{model}) does not divide {n} devices")
    data = n // (model * pods * pipe * expert)
    shape = (pods, pipe, data, expert, model)
    axes = ("pod", "pipe", "data", "expert", "model")
    keep = [i for i, (a, s) in enumerate(zip(axes, shape))
            if a in ("data", "model") or s > 1]
    shape = tuple(shape[i] for i in keep)
    axes = tuple(axes[i] for i in keep)
    from repro.core.compat import abstract_mesh, make_mesh
    return (abstract_mesh if abstract else make_mesh)(shape, axes)
