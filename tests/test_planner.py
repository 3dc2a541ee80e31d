"""CAT planner: design-case reproduction + property tests."""
import hypothesis.strategies as st
import pytest
from hypothesis import given, settings

from repro.configs import ALL_ARCHS, get_config
from repro.core.hardware import TPU_V5E
from repro.core.plan import (
    PRG_MAX_PIPELINE_DEPTH,
    SPATIAL,
    TEMPORAL,
    derive_plan,
    derive_serve_plan,
    design_case_vck5000,
    serve_feasible,
)
from repro.core.pu import derive_pu_family, is_compute_bound, pick_pu, solve_mm_tiles


class TestDesignCase:
    """Paper §V.B BERT-Base walk-through on VCK5000 numbers."""

    def test_factor1_matches_paper(self):
        dc = design_case_vck5000()
        # paper reports Factor1 ~= 1.5 (4 LBs of 256x768x768 over the engine)
        assert 1.3 <= dc["factor1"] <= 1.6
        assert dc["factor1"] < PRG_MAX_PIPELINE_DEPTH

    def test_factor2_matches_paper(self):
        dc = design_case_vck5000()
        # paper reports 7.5625 MB < 23.9 MB SRAM
        assert 7.0 <= dc["factor2_mb"] <= 8.5
        assert dc["factor2_mb"] < dc["buffer_budget_mb"]

    def test_p_atb_is_4(self):
        assert design_case_vck5000()["p_atb"] == 4

    def test_fully_pipelined_mode_selected(self):
        assert design_case_vck5000()["mode"] == SPATIAL


class TestPUFamily:
    def test_three_specs(self):
        fam = derive_pu_family(TPU_V5E)
        assert set(fam) == {"LARGE", "STANDARD", "SMALL"}
        assert fam["LARGE"].vmem_bytes <= TPU_V5E.vmem_bytes
        # LARGE and STANDARD must be compute-bound (Eq. 4')
        assert is_compute_bound(fam["LARGE"], TPU_V5E)
        assert is_compute_bound(fam["STANDARD"], TPU_V5E)

    def test_mxu_alignment(self):
        for s in solve_mm_tiles(TPU_V5E):
            assert s.block_m % TPU_V5E.mxu_dim == 0
            assert s.block_n % TPU_V5E.mxu_dim == 0

    def test_small_model_gets_small_pu(self):
        little = pick_pu(197, 64, 768)
        big = pick_pu(8192, 8192, 8192)
        assert little.block_n <= big.block_n

    @given(
        m=st.integers(1, 1 << 15),
        n=st.integers(1, 1 << 15),
        k=st.integers(1, 1 << 15),
    )
    @settings(max_examples=50, deadline=None)
    def test_pick_pu_total(self, m, n, k):
        s = pick_pu(m, n, k)
        assert s.vmem_bytes <= TPU_V5E.vmem_bytes


MESHES = [
    {"data": 16, "model": 16},
    {"pod": 2, "data": 16, "model": 16},
    {"data": 1, "model": 1},
    {"data": 4, "model": 8},
]


class TestDerivePlan:
    @pytest.mark.parametrize("arch", ALL_ARCHS)
    @pytest.mark.parametrize("mesh", MESHES[:2], ids=["single", "multi"])
    def test_plan_derives_for_all(self, arch, mesh):
        cfg = get_config(arch)
        plan = derive_plan(cfg, mesh, batch=256, seq_len=4096)
        assert plan.mha.mode in (SPATIAL, TEMPORAL)
        assert plan.microbatches >= 1
        assert plan.p_atb >= 1
        # head shards must divide heads
        if plan.head_shards > 1:
            assert cfg.n_heads % plan.head_shards == 0

    def test_spatial_requires_divisibility(self):
        cfg = get_config("smollm-135m")  # 9 heads % 16 != 0
        plan = derive_plan(cfg, MESHES[0], batch=256, seq_len=4096)
        assert plan.mha.mode == TEMPORAL
        cfg2 = get_config("qwen3-1.7b")  # 16 heads % 16 == 0, Factor1 < depth
        plan2 = derive_plan(cfg2, MESHES[0], batch=256, seq_len=4096)
        assert plan2.mha.mode == SPATIAL

    def test_factor1_rule_picks_temporal_for_huge_dense(self):
        """Paper Eq.5/6 (§Perf iteration 6): Factor1 >= PRG depth -> mode (2)
        serial/FSDP, even though TP divisibility holds (123B dense)."""
        cfg = get_config("mistral-large-123b")
        plan = derive_plan(cfg, MESHES[0], batch=256, seq_len=4096)
        assert plan.mha.factor1 >= 4
        assert plan.mha.mode == TEMPORAL
        assert plan.dp_over_model and plan.zero_weights
        # inference keeps the spatial/TP plan (latency-optimal weights-resident)
        plan_inf = derive_plan(
            cfg, MESHES[0], batch=128, seq_len=32768, training=False
        )
        assert plan_inf.mha.mode == SPATIAL

    def test_temporal_folds_model_into_dp(self):
        cfg = get_config("smollm-135m")
        plan = derive_plan(cfg, MESHES[0], batch=256, seq_len=4096)
        assert plan.dp_over_model  # 256 % (16*16) == 0

    def test_moe_modes(self):
        p128 = derive_plan(get_config("qwen3-moe-30b-a3b"), MESHES[0], batch=256, seq_len=4096)
        assert p128.moe_mode == "ep"  # 128 experts / 16
        p8 = derive_plan(get_config("mixtral-8x7b"), MESHES[0], batch=256, seq_len=4096)
        assert p8.moe_mode == "tp"  # 8 experts < 16 but d_ff 14336 % 16 == 0

    def test_seq_shard_for_long_context(self):
        cfg = get_config("rwkv6-1.6b")
        plan = derive_plan(cfg, MESHES[0], batch=1, seq_len=524288, training=False)
        assert plan.seq_shard

    @given(
        batch=st.sampled_from([1, 8, 32, 128, 256, 512]),
        seq=st.sampled_from([128, 2048, 4096, 32768]),
        arch=st.sampled_from(list(ALL_ARCHS)),
    )
    @settings(max_examples=40, deadline=None)
    def test_plan_is_deterministic_and_total(self, batch, seq, arch):
        cfg = get_config(arch)
        p1 = derive_plan(cfg, MESHES[0], batch=batch, seq_len=seq)
        p2 = derive_plan(cfg, MESHES[0], batch=batch, seq_len=seq)
        assert p1 == p2  # pure function of its inputs
        assert batch % p1.microbatches == 0 or p1.microbatches == 1


SERVABLE = [a for a in ALL_ARCHS if serve_feasible(get_config(a))[0]]


class TestKernelTile:
    """The paged kernel's table tile (``ServePlan.pages_per_tile``): derived
    from the chip and the shapes, a divisor of the table width."""

    def test_qwen3_on_v5e_fills_the_lanes(self):
        sp = derive_serve_plan(get_config("qwen3-1.7b"), MESHES[2], TPU_V5E)
        assert sp.max_blocks_per_seq % sp.pages_per_tile == 0
        # a tile of keys fills a vreg's 128 lanes
        assert sp.pages_per_tile * sp.block_size >= 128

    @pytest.mark.parametrize("max_seq_len", [24, 64, 200])
    def test_tiny_table_gets_a_divisor(self, max_seq_len):
        cfg = get_config("smollm-135m").reduced()
        sp = derive_serve_plan(
            cfg, MESHES[2], max_seq_len=max_seq_len, decode_batch=4, block_size=8,
            prefill_chunk=8,
        )
        assert 1 <= sp.pages_per_tile <= sp.max_blocks_per_seq
        assert sp.max_blocks_per_seq % sp.pages_per_tile == 0


class TestStepTokens:
    """The mixed step's token budget T (``ServePlan.step_tokens``): derived
    from B, W and gamma, never set by hand."""

    def test_qwen3_on_v5e_is_1152(self):
        sp = derive_serve_plan(get_config("qwen3-1.7b"), MESHES[2], TPU_V5E)
        assert (sp.decode_batch, sp.mixed_slab_width, sp.spec_len) == (32, 256, 0)
        # 32 decode rows + four 256-row chunks = 1056 -> the next multiple of 128
        assert sp.step_tokens == 1152
        assert sp.to_record()["step_tokens"] == 1152
        assert "step_tokens=1152" in sp.describe()

    @pytest.mark.parametrize("arch", SERVABLE)
    @pytest.mark.parametrize("mesh", [MESHES[2], MESHES[0]], ids=["one", "multi"])
    @pytest.mark.parametrize("draft", ["none", "ngram"])
    def test_budget_holds_every_runner_and_a_chunk(self, arch, mesh, draft):
        sp = derive_serve_plan(get_config(arch), mesh, TPU_V5E, draft=draft)
        B, W, g, T = sp.decode_batch, sp.mixed_slab_width, sp.spec_len, sp.step_tokens
        # every running slot's rows and four prefill chunks fit, except
        # where the whole slab is smaller: no step can outgrow B x W
        assert T >= min(B * (1 + g) + 4 * W, B * W)
        assert T % 128 == 0 or T == B * W
        assert T <= B * W

    def test_small_slab_caps_the_budget(self):
        cfg = get_config("smollm-135m").reduced()
        sp = derive_serve_plan(
            cfg, MESHES[2], max_seq_len=64, decode_batch=4, prefill_chunk=8
        )
        assert sp.step_tokens == 4 * 8  # the whole slab, not 128 rows
