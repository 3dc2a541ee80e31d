"""Hardware registry, degenerate-device hazards, and the energy table."""
import dataclasses
import math

import pytest

from repro.core.hardware import (
    ENERGY_PJ,
    HARDWARE_VARIANTS,
    TPU_V5E,
    VCK5000,
    HardwareSpec,
    energy_params,
    get_hardware,
    register_variant,
    registered_hardware,
)
from repro.core.plan import derive_plan, derive_serve_plan
from repro.core.pu import pick_pu
from repro.configs import get_config


# ---------------------------------------------------------------- registry
def test_registry_resolves_builtin_devices():
    assert get_hardware("tpu_v5e") is TPU_V5E
    assert get_hardware("vck5000") is VCK5000


def test_unknown_name_lists_registered_variants():
    with pytest.raises(KeyError) as e:
        get_hardware("tpu_v9")
    msg = str(e.value)
    assert "tpu_v5e" in msg and "vck5000" in msg


def test_declared_variants_are_registered():
    names = registered_hardware()
    for name in HARDWARE_VARIANTS:
        assert name in names
    hbm2x = get_hardware("tpu_v5e-hbm2x")
    assert hbm2x.hbm_bandwidth == pytest.approx(2 * TPU_V5E.hbm_bandwidth)
    # non-replaced fields inherit from the base spec
    assert hbm2x.hbm_bytes == TPU_V5E.hbm_bytes


def test_register_variant_replaces_fields_only():
    v = register_variant("tpu_v5e-testonly", "tpu_v5e", tdp_watts=1.0)
    assert v.tdp_watts == 1.0
    assert v.peak_flops_bf16 == TPU_V5E.peak_flops_bf16
    assert get_hardware("tpu_v5e-testonly") is v


# ------------------------------------------------- degenerate-device hazards
def _degenerate(**kw) -> HardwareSpec:
    base = dict(
        name="degenerate",
        peak_flops_bf16=1e12,
        peak_ops_int8=2e12,
        vmem_bytes=1 << 20,
        hbm_bytes=16 * 1024**3,
        hbm_bandwidth=0.0,
        ici_bandwidth_per_link=0.0,
        ici_links_per_chip=0,
    )
    base.update(kw)
    return HardwareSpec(**base)


def test_zero_bandwidth_machine_balance_is_inf():
    hw = _degenerate()
    assert math.isinf(hw.machine_balance_bf16)
    assert hw.ici_bandwidth == 0.0


def test_zero_bandwidth_matmul_time_is_inf_not_crash():
    assert math.isinf(_degenerate().matmul_time_s(128, 128, 128))


def test_planner_total_on_degenerate_device():
    """derive_plan / derive_serve_plan / pick_pu must not divide by zero on
    a device with no HBM bandwidth or no interconnect (VCK5000 ships
    ici_links_per_chip=0; SRAM-only variants ship hbm_bandwidth=0)."""
    cfg = get_config("smollm-135m")
    hw = _degenerate()
    mesh = {"data": 1, "model": 1}
    plan = derive_plan(cfg, mesh, hw, batch=4, seq_len=64, training=False)
    assert plan is not None
    serve = derive_serve_plan(cfg, mesh, hw, max_seq_len=128)
    assert serve.decode_batch >= 1
    tile = pick_pu(8, cfg.d_model, cfg.d_model, hw, dtype_bytes=2)
    assert tile.block_m >= 1


def test_vck5000_no_ici_paths_total():
    cfg = get_config("smollm-135m")
    serve = derive_serve_plan(cfg, {"data": 1, "model": 1}, VCK5000,
                              max_seq_len=256)
    assert serve.decode_batch >= 1
    assert VCK5000.ici_bandwidth == 0.0


# ------------------------------------------------------------ energy table
def test_energy_params_merges_node_row_with_overrides():
    ep = energy_params(VCK5000)
    assert ep["mem_byte"] == 150.0  # device override wins
    assert ep["flop_bf16"] == ENERGY_PJ["7nm"]["flop_bf16"]  # node row


def test_energy_params_empty_without_tech_node():
    hw = _degenerate(tech_node="")
    assert energy_params(hw) == {}
    hw2 = dataclasses.replace(hw, energy_pj=(("mem_byte", 9.0),))
    assert energy_params(hw2) == {"mem_byte": 9.0}


def test_spec_stays_hashable():
    hash(TPU_V5E)
    hash(get_hardware("vck5000-int8w"))


def test_device_kind_resolves_the_chip_spec():
    """On a TPU the chip's device_kind picks the spec; an unknown kind
    raises instead of planning against some other chip's numbers."""
    from repro.core.hardware import hardware_for_kind, resolve_hardware

    assert hardware_for_kind("TPU v5 lite") is TPU_V5E
    with pytest.raises(KeyError, match="TPU v9 imaginary"):
        hardware_for_kind("TPU v9 imaginary")
    # off-TPU the named registered device is the planning target
    assert resolve_hardware("vck5000").name == "vck5000"
