"""TPU slice topology catalog and logical mesh specs.

This is the TPU-native replacement for the reference's GPU-count resource
model (`nvidia.com/gpu` detection, pkg/util/resource_utils/resources.go:69-123)
and its port/hostfile communication wiring (SURVEY.md §2.5): jobs declare a
*slice* (an atomically-allocated ICI domain) and a *logical mesh* laid over
it; the operator's job is to hand every worker its coordinates so
`jax.distributed.initialize` + `jax.sharding.Mesh` can do the rest.

Conventions:

- A slice is named ``<generation>-<chips>`` (v5e-32 = 32 chips). One *pod*
  (process) runs per TPU host; hosts within a slice are wired by ICI (no
  ports to allocate), slices are wired to each other over DCN (multislice).
- ``physical_mesh`` is the chip grid (e.g. 4x8 for v5e-32); logical mesh
  axes (data/fsdp/tensor/sequence/expert) are laid over it so that
  the most communication-hungry axis rides the fastest ICI dimension.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple


@dataclass(frozen=True)
class SliceTopology:
    """One atomically-schedulable TPU slice."""

    name: str  # e.g. "v5e-32"
    chips: int
    hosts: int  # pods per slice == hosts
    chips_per_host: int
    physical_mesh: Tuple[int, ...]  # chip grid, e.g. (4, 8)
    #: Per-chip peak bf16 TFLOP/s — used for MFU accounting, not scheduling.
    peak_bf16_tflops: float = 197.0
    hbm_gib_per_chip: float = 16.0
    #: Per-chip HBM bandwidth GB/s (spec sheet) — used for bench sanity
    #: floors (a training step cannot beat one full param read from HBM).
    hbm_gbps: float = 819.0
    #: Per-chip aggregate ICI bandwidth GB/s (sum over links, one
    #: direction) — prices intra-slice collectives in the auto-parallelism
    #: planner's cost model (kubedl_tpu/planner/costmodel.py).
    ici_gbps: float = 180.0
    #: Per-chip DCN bandwidth GB/s — prices cross-slice (multislice)
    #: collectives; one to two orders of magnitude below ICI, which is why
    #: only the outermost (replica) mesh axis may cross slices.
    dcn_gbps: float = 6.25

    @property
    def total_devices(self) -> int:
        return self.chips

    def coordinates(self, host_index: int) -> Tuple[int, ...]:
        """Host coordinate within the slice's host grid (row-major)."""
        hosts_mesh = self.host_mesh()
        coord = []
        rem = host_index
        for dim in reversed(hosts_mesh):
            coord.append(rem % dim)
            rem //= dim
        return tuple(reversed(coord))

    def host_mesh(self) -> Tuple[int, ...]:
        """Host grid: physical mesh divided by the per-host chip block.

        v5e hosts own a 2x2 chip block; we fold chips_per_host into the last
        axes of the physical mesh.
        """
        rem = self.chips_per_host
        dims = list(self.physical_mesh)
        # Peel factors of 2 round-robin across dims (last dim first) so the
        # host block comes out near-square (v5e: 2x2), matching hardware.
        i = len(dims) - 1
        stuck = 0
        while rem > 1 and stuck < len(dims):
            if dims[i] % 2 == 0:
                dims[i] //= 2
                rem //= 2
                stuck = 0
            else:
                stuck += 1
            i = (i - 1) % len(dims)
        if rem > 1:  # non-power-of-two remainder: divide any divisible dim
            for j, d in enumerate(dims):
                g = math.gcd(d, rem)
                dims[j] //= g
                rem //= g
        return tuple(dims)


#: Catalog of schedulable slice shapes. Peak flops: v4 ~275 bf16 TFLOP/s,
#: v5e ~197, v5p ~459 (public spec-sheet numbers).
SLICE_CATALOG: Dict[str, SliceTopology] = {}


def _register(*topos: SliceTopology) -> None:
    for t in topos:
        SLICE_CATALOG[t.name] = t


_register(
    # v5e: 1 host = 4 chips (2x2), 197 bf16 TFLOP/s, 16 GiB HBM
    SliceTopology("v5e-4", 4, 1, 4, (2, 2), 197.0, 16.0, 819.0, 180.0, 6.25),
    SliceTopology("v5e-8", 8, 2, 4, (2, 4), 197.0, 16.0, 819.0, 180.0, 6.25),
    SliceTopology("v5e-16", 16, 4, 4, (4, 4), 197.0, 16.0, 819.0, 180.0, 6.25),
    SliceTopology("v5e-32", 32, 8, 4, (4, 8), 197.0, 16.0, 819.0, 180.0, 6.25),
    SliceTopology("v5e-64", 64, 16, 4, (8, 8), 197.0, 16.0, 819.0, 180.0, 6.25),
    SliceTopology("v5e-128", 128, 32, 4, (8, 16), 197.0, 16.0, 819.0, 180.0, 6.25),
    SliceTopology("v5e-256", 256, 64, 4, (16, 16), 197.0, 16.0, 819.0, 180.0, 6.25),
    # v4: 1 host = 4 chips, 3D torus, 275 bf16 TFLOP/s, 32 GiB
    SliceTopology("v4-8", 8, 1, 4, (2, 2, 1), 275.0, 32.0, 1228.0, 270.0, 6.25),
    SliceTopology("v4-16", 16, 2, 4, (2, 2, 2), 275.0, 32.0, 1228.0, 270.0, 6.25),
    SliceTopology("v4-32", 32, 4, 4, (2, 2, 4), 275.0, 32.0, 1228.0, 270.0, 6.25),
    SliceTopology("v4-64", 64, 8, 4, (2, 4, 4), 275.0, 32.0, 1228.0, 270.0, 6.25),
    # v5p: 1 host = 4 chips, 459 bf16 TFLOP/s, 95 GiB
    SliceTopology("v5p-8", 8, 2, 4, (2, 2, 1), 459.0, 95.0, 2765.0, 540.0, 6.25),
    SliceTopology("v5p-16", 16, 4, 4, (2, 2, 2), 459.0, 95.0, 2765.0, 540.0, 6.25),
    SliceTopology("v5p-32", 32, 8, 4, (2, 2, 4), 459.0, 95.0, 2765.0, 540.0, 6.25),
    # v6e (Trillium): 1 host = 4 chips, ~918 bf16 TFLOP/s, 32 GiB
    SliceTopology("v6e-4", 4, 1, 4, (2, 2), 918.0, 32.0, 1640.0, 360.0, 12.5),
    SliceTopology("v6e-8", 8, 2, 4, (2, 4), 918.0, 32.0, 1640.0, 360.0, 12.5),
    SliceTopology("v6e-16", 16, 4, 4, (4, 4), 918.0, 32.0, 1640.0, 360.0, 12.5),
    SliceTopology("v6e-32", 32, 8, 4, (4, 8), 918.0, 32.0, 1640.0, 360.0, 12.5),
    # CPU stand-in used by tests / kind-style local clusters
    SliceTopology("cpu-1", 1, 1, 1, (1,), 0.5, 8.0, 50.0, 1.0, 0.5),
    SliceTopology("cpu-8", 8, 8, 1, (8,), 0.5, 8.0, 50.0, 1.0, 0.5),
)


#: device_kind substrings (as PJRT reports them) -> catalog generation token
_DEVICE_KIND_ALIASES = {
    "v5 lite": "v5e", "v5litepod": "v5e", "v5e": "v5e",
    "v6 lite": "v6e", "v6e": "v6e",
    "v5p": "v5p",
    "v4": "v4",
}


def _catalog_lookup(kind: str, getter) -> float:
    """Resolve a PJRT device_kind string to a per-chip spec value via the
    slice catalog (single source of truth for hardware numbers). 0.0 for
    what is not a TPU; a TPU kind the table does not know raises — an MFU
    of 0 and no HBM floor would pass for a measurement."""
    kind = kind.lower()
    gens = {t.name.split("-")[0]: getter(t) for t in SLICE_CATALOG.values()}
    for sub, gen in _DEVICE_KIND_ALIASES.items():
        if sub in kind and gen in gens:
            return gens[gen]
    if kind.startswith("tpu"):
        raise ValueError(
            f"TPU device kind {kind!r} is not in the slice catalog "
            f"(known: {sorted(_DEVICE_KIND_ALIASES)})"
        )
    return 0.0


def peak_flops_for_device_kind(kind: str) -> float:
    """Per-chip peak bf16 FLOP/s — used for MFU accounting."""
    return _catalog_lookup(kind, lambda t: t.peak_bf16_tflops * 1e12)


def hbm_bandwidth_for_device_kind(kind: str) -> float:
    """Per-chip HBM bandwidth bytes/s — used for bench sanity floors."""
    return _catalog_lookup(kind, lambda t: t.hbm_gbps * 1e9)


def get_slice(name: str) -> SliceTopology:
    try:
        return SLICE_CATALOG[name]
    except KeyError:
        raise KeyError(
            f"unknown slice type {name!r}; known: {sorted(SLICE_CATALOG)}"
        ) from None


@dataclass
class MeshSpec:
    """Logical mesh laid over one or more slices.

    The operator passes this down as the `KUBEDL_MESH_AXES` env hint; the
    in-process training harness (`kubedl_tpu.parallel.mesh`) turns it into a
    concrete `jax.sharding.Mesh`. Axis order is outermost-first; by
    convention DCN-crossing axes (data across slices) come first and
    ICI-hungry axes (tensor) last, matching the scaling-book recipe.
    """

    axes: Dict[str, int] = field(default_factory=dict)

    #: outermost-first; DCN-crossing (replica/data) out, ICI-hungry in.
    #: "sp" = sequence/context parallel (ring attention), "pipe" = pipeline
    #: stages, "expert" = MoE expert parallel.
    AXIS_ORDER = ("replica", "data", "fsdp", "pipe", "expert", "sp", "tensor")

    def size(self) -> int:
        n = 1
        for v in self.axes.values():
            n *= v
        return n

    def ordered(self) -> List[Tuple[str, int]]:
        known = [(a, self.axes[a]) for a in self.AXIS_ORDER if a in self.axes]
        extra = [(a, v) for a, v in self.axes.items() if a not in self.AXIS_ORDER]
        return known + extra

    def to_env(self) -> str:
        return ",".join(f"{a}={v}" for a, v in self.ordered())

    @classmethod
    def from_env(cls, s: str) -> "MeshSpec":
        axes: Dict[str, int] = {}
        for part in filter(None, (p.strip() for p in s.split(","))):
            k, _, v = part.partition("=")
            axes[k] = int(v)
        return cls(axes=axes)

    @classmethod
    def for_slice(
        cls, topo: SliceTopology, tensor: int = 1, num_slices: int = 1
    ) -> "MeshSpec":
        """Default mesh: pure data parallel over chips, optionally carving a
        tensor axis out of the fastest ICI dimension; multislice adds an
        outer DCN data axis."""
        chips = topo.chips * num_slices
        if chips % tensor:
            raise ValueError(f"tensor={tensor} does not divide {chips} chips")
        axes: Dict[str, int] = {}
        if num_slices > 1:
            axes["replica"] = num_slices
            chips //= num_slices
        axes["data"] = chips // tensor
        if tensor > 1:
            axes["tensor"] = tensor
        return cls(axes=axes)


def validate_mesh_for_slice(
    mesh: MeshSpec, topo: SliceTopology, num_slices: int = 1
) -> Optional[str]:
    """Return an error message if the logical mesh cannot tile the slice.

    Checked at job admission (workloads validate) so a bad mesh is rejected
    on submit instead of failing inside the worker at ``build_mesh`` time.
    """
    for axis, size in mesh.axes.items():
        if axis not in MeshSpec.AXIS_ORDER:
            return (
                f"unknown mesh axis {axis!r}; known axes: "
                + ", ".join(MeshSpec.AXIS_ORDER)
            )
        if size < 1:
            return f"mesh axis {axis}={size} must be >= 1"
    want = topo.chips * num_slices
    if mesh.size() != want:
        return f"mesh covers {mesh.size()} devices but topology has {want} chips"
    return None
