"""Hardware description for the CAT planner.

The paper's "Intrinsic hardware parameters" (Table III) — AIE Window size,
PLIO bandwidth, core count, on-chip buffer — become the TPU-chip analogues
below.  Everything the planner decides is a pure function of
(ArchConfig, Mesh, HardwareSpec), which is the paper's top-down customization
contract: the underlying hardware and the upper model jointly constrain the
customizable attributes.

Since the family planner (core/search.py) the spec also carries the *cost*
side of the contract — TDP, rental price, and a per-op dynamic-energy table
keyed by tech node (the BCE-table idiom: a dict of per-node constants, each
device naming its node and optionally overriding single entries).  Devices
live in a registry: ``get_hardware`` resolves any registered name, and
variant devices (a bandwidth-doubled v5e, an int8-heavy VCK5000 analog) are
declarative ``HARDWARE_VARIANTS`` entries, not code.  Field-by-field
reference with the paper Table III analogies: docs/PLANNER.md.
"""
from __future__ import annotations

import dataclasses
import math

import jax


@dataclasses.dataclass(frozen=True)
class HardwareSpec:
    """Per-chip + interconnect constants of the target platform."""

    name: str
    # Compute (paper: AIE core count x per-core throughput).
    peak_flops_bf16: float  # FLOP/s per chip
    peak_ops_int8: float  # OP/s per chip
    # Memory hierarchy (paper: AIE Window / PL BRAM+URAM / DDR).
    vmem_bytes: int  # on-chip vector memory per chip  (AIE Window analog)
    hbm_bytes: int  # off-chip HBM capacity per chip   (DDR analog)
    hbm_bandwidth: float  # bytes/s per chip             (DDR bandwidth analog)
    # Interconnect (paper: PLIO / NoC).
    ici_bandwidth_per_link: float  # bytes/s per ICI link
    ici_links_per_chip: int  # links per chip on a torus axis pair
    # MXU native tile edge (paper: AIE vector instruction length, power of 2).
    mxu_dim: int = 128
    # Host dispatch overhead per device program launch (runtime call +
    # host-side scheduling between steps).  The paper's AIE pipeline streams
    # many iterations per host intervention precisely because this cost is
    # fixed per dispatch; the serving planner uses it to size the rolled
    # on-device decode loop (``ServePlan.rolled_steps``).
    dispatch_overhead_s: float = 100e-6
    # ---- Cost / energy (family-search axes; docs/PLANNER.md) --------------
    # Board/chip power envelope; with no per-op energy table the search
    # charges tdp_watts for the full step (power-model fallback).
    tdp_watts: float = 0.0
    # Rental/amortized price per chip-hour ($/token numerator).  0 = free
    # (the device never appears on the $/token axis).
    dollars_per_hour: float = 0.0
    # Tech node naming a row of ENERGY_PJ (per-op dynamic energy, the
    # BCE-table idiom).  "" = no table; the search falls back to TDP.
    tech_node: str = ""
    # Per-device overrides of single ENERGY_PJ entries, e.g. a DDR-attached
    # device re-pricing "mem_byte".  Tuple-of-pairs so the spec stays
    # hashable (plans ride as static jit arguments).
    energy_pj: tuple[tuple[str, float], ...] = ()

    @property
    def machine_balance_bf16(self) -> float:
        """FLOPs per HBM byte needed to stay compute bound (Eq. 4 analog;
        docs/ARCHITECTURE.md).  ``inf`` for a device with no off-chip
        bandwidth (degenerate SRAM-only variants): every tile is then
        bandwidth-starved and no shape is compute-bound."""
        if self.hbm_bandwidth <= 0:
            return math.inf
        return self.peak_flops_bf16 / self.hbm_bandwidth

    @property
    def ici_bandwidth(self) -> float:
        """Aggregate interconnect bytes/s per chip (0 = single device)."""
        return self.ici_bandwidth_per_link * self.ici_links_per_chip

    def matmul_time_s(self, m: int, n: int, k: int, dtype_bytes: int = 2) -> float:
        """Roofline time for one MxKxN matmul on one chip."""
        flops = 2.0 * m * n * k
        peak = self.peak_flops_bf16 if dtype_bytes >= 2 else self.peak_ops_int8
        t_compute = flops / peak if peak > 0 else math.inf
        bytes_moved = dtype_bytes * (m * k + k * n + m * n)
        t_memory = (
            bytes_moved / self.hbm_bandwidth if self.hbm_bandwidth > 0 else math.inf
        )
        return max(t_compute, t_memory)


# Per-op dynamic energy by tech node, picojoules (the lumos/BCE-table idiom:
# one table row per node, devices reference a row by name).  Values are
# order-of-magnitude engineering constants — bf16 MAC ~1 pJ/FLOP at 7 nm,
# HBM2e access ~4 pJ/bit, inter-chip serdes ~3x on-package DRAM — chosen so
# the *ratios* (compute vs memory vs wire, 7 nm vs 16 nm) are right; absolute
# J/token from the search is a model, not a measurement.  "static_fraction"
# is the share of TDP burned regardless of activity (leakage + clocks +
# uncore), charged per second of step time.
ENERGY_PJ: dict[str, dict[str, float]] = {
    "7nm": {
        "flop_bf16": 0.8,
        "op_int8": 0.2,
        "mem_byte": 35.0,
        "ici_byte": 90.0,
        "static_fraction": 0.35,
    },
    # Dennard-scaled ancestor node for what-if variants: dynamic energy
    # roughly 2.2x the 7 nm row, leakier static share.
    "16nm": {
        "flop_bf16": 1.8,
        "op_int8": 0.45,
        "mem_byte": 40.0,
        "ici_byte": 110.0,
        "static_fraction": 0.45,
    },
}


def energy_params(hw: HardwareSpec) -> dict[str, float]:
    """Resolved per-op energy table for a device: its tech-node row overlaid
    with the device's own ``energy_pj`` overrides.  Empty dict = no table
    (callers fall back to the TDP power model)."""
    table = dict(ENERGY_PJ.get(hw.tech_node, {}))
    table.update(dict(hw.energy_pj))
    return table


# TPU v5e constants per the task spec (197 TFLOP/s bf16, 819 GB/s HBM,
# ~50 GB/s/link ICI); VMEM/HBM capacities are the public v5e numbers.
# TDP and $/hr are public-ballpark serving figures (docs/PLANNER.md).
TPU_V5E = HardwareSpec(
    name="tpu_v5e",
    peak_flops_bf16=197e12,
    peak_ops_int8=394e12,
    vmem_bytes=128 * 1024 * 1024,
    hbm_bytes=16 * 1024**3,
    hbm_bandwidth=819e9,
    ici_bandwidth_per_link=50e9,
    ici_links_per_chip=4,
    tdp_watts=215.0,
    dollars_per_hour=1.20,
    tech_node="7nm",
)

# The paper's platform, kept for the Table VI/VII benchmark analogs
# (VCK5000: 400 AIE cores, 145 TOPS int8, 23.9 MB SRAM @ 23.5 TB/s,
#  16 GB DDR @ 102.4 GB/s; Versal ACAP is TSMC 7 nm).  DDR4 access energy
# is far above HBM, hence the per-device "mem_byte" override.
VCK5000 = HardwareSpec(
    name="vck5000",
    peak_flops_bf16=145e12 / 4,  # no native bf16 MM at full rate; int8 is the paper's mode
    peak_ops_int8=145e12,
    vmem_bytes=int(23.9e6),
    hbm_bytes=16 * 1024**3,
    hbm_bandwidth=102.4e9,
    ici_bandwidth_per_link=0.0,  # single device
    ici_links_per_chip=0,
    tdp_watts=225.0,
    dollars_per_hour=0.35,  # card price amortized over ~3y of service
    tech_node="7nm",
    energy_pj=(("mem_byte", 150.0),),
)

DEFAULT_HARDWARE = TPU_V5E

_REGISTRY: dict[str, HardwareSpec] = {}


def register_hardware(spec: HardwareSpec) -> HardwareSpec:
    """Add a device to the registry ``get_hardware`` resolves.  Re-registering
    a name replaces it (tests register throwaway variants)."""
    _REGISTRY[spec.name] = spec
    return spec


def register_variant(name: str, base: str, **fields) -> HardwareSpec:
    """Declare a device variant: the ``base`` spec with ``fields`` replaced.

    This is how the family search gets its hardware axis — a variant is
    data, not a subclass (docs/PLANNER.md "Adding a device variant")."""
    return register_hardware(
        dataclasses.replace(get_hardware(base), name=name, **fields)
    )


def get_hardware(name: str) -> HardwareSpec:
    if name not in _REGISTRY:
        raise KeyError(
            f"unknown hardware {name!r}; registered: {sorted(_REGISTRY)}"
        )
    return _REGISTRY[name]


# ``device_kind`` as JAX reports it -> the registered spec of that chip.
DEVICE_KINDS: dict[str, str] = {
    "TPU v5 lite": "tpu_v5e",
}


def hardware_for_kind(device_kind: str) -> HardwareSpec:
    """The spec of a chip by its ``device_kind``; an unknown kind raises
    rather than planning against some other chip's numbers."""
    if device_kind not in DEVICE_KINDS:
        raise KeyError(
            f"no HardwareSpec for device kind {device_kind!r}; "
            f"known kinds: {sorted(DEVICE_KINDS)}"
        )
    return get_hardware(DEVICE_KINDS[device_kind])


def resolve_hardware(planning_target: str) -> HardwareSpec:
    """The spec the planner sizes against.  On a TPU backend the chip the
    program runs on decides; elsewhere (CPU runs, dry-runs) the named
    registered device is the planning target."""
    if jax.default_backend() == "tpu":
        return hardware_for_kind(jax.devices()[0].device_kind)
    return get_hardware(planning_target)


def registered_hardware() -> tuple[str, ...]:
    """Names the family search can sweep (sorted, deterministic)."""
    return tuple(sorted(_REGISTRY))


register_hardware(TPU_V5E)
register_hardware(VCK5000)

# Variant devices as declarative data: (base, replaced fields).  Each is an
# analytic what-if the family search can answer — "what does the frontier
# look like if HBM keeps up / on a cheaper serving bin / with the paper's
# int8 mode doubled" — not a claim about a shipping SKU.
HARDWARE_VARIANTS: dict[str, tuple[str, dict]] = {
    # Bandwidth-doubled v5e: decode is weight-stream-bound, so this is the
    # highest-leverage single knob for tokens/s.
    "tpu_v5e-hbm2x": (
        "tpu_v5e",
        dict(hbm_bandwidth=1638e9, tdp_watts=240.0, dollars_per_hour=1.45),
    ),
    # Serving-binned v5e: half the MXU clock, ~2/3 power, ~half price —
    # decode rarely misses the FLOPs, the $/token axis does notice.
    "tpu_v5e-lite": (
        "tpu_v5e",
        dict(
            peak_flops_bf16=98.5e12,
            peak_ops_int8=197e12,
            tdp_watts=150.0,
            dollars_per_hour=0.65,
        ),
    ),
    # Int8-heavy VCK5000 analog: the paper's int8 deployment mode with the
    # AIE array doubled toward int8 MACs.
    "vck5000-int8w": (
        "vck5000",
        dict(peak_ops_int8=290e12, tdp_watts=300.0),
    ),
}

for _name, (_base, _delta) in HARDWARE_VARIANTS.items():
    register_variant(_name, _base, **_delta)
