"""The CAT customization strategy: derive an accelerator instance from
(model config, mesh, hardware).

Paper §IV: three customizable attributes are decided top-down —
  1. AIE MM PU scale        -> per-MM-site Pallas tile specs (core/pu.py)
  2. Parallel mode (Eq.5/6) -> SPATIAL (TP, fully-pipelined analog) vs
                               TEMPORAL (ZeRO-DP, serial-using-all-resources
                               analog), plus remat/microbatch from Factor2'
  3. ATB parallelism (Eq.7/8) -> attention head-shard degree P_ATB

The plan is a frozen dataclass: a pure function of its inputs, hashable, and
used as a static argument of jitted step functions.  `design_case_vck5000`
reproduces the paper's §V.B BERT-Base walk-through numbers (Factor1 ~= 1.5,
Factor2 ~= 7.56 MB) on the paper's own hardware constants.

The plan is the system's control plane: every field here is consumed by an
executor — `dist.sharding.Shardings` (specs), `train/train_step.py`
(microbatching, gradient wire format, pipeline routing), and
`models/transformer.py` (SP layer stack).  Paper-to-code map with the
equation cross-references: docs/ARCHITECTURE.md.
"""
from __future__ import annotations

import dataclasses
from typing import Mapping, Optional

import numpy as np

from repro.core.hardware import DEFAULT_HARDWARE, VCK5000, HardwareSpec
from repro.core.pu import MMTileSpec, derive_pu_family, pick_pu

# Paper constant: EDPU pipeline has at most 4 PRGs in flight per stage.
PRG_MAX_PIPELINE_DEPTH = 4

SPATIAL = "spatial"  # paper parallel mode (1): fully-pipelined, sliced fabric
TEMPORAL = "temporal"  # paper parallel mode (2): serial PRGs, each uses all chips


@dataclasses.dataclass(frozen=True)
class StagePlan:
    """Per-stage (MHA / FFN) decision record."""

    mode: str  # SPATIAL | TEMPORAL
    factor1: float  # Eq.5/6 Factor1 analog (diagnostic, logged)
    factor2_bytes: int  # Eq.5/6 Factor2 analog: activation bytes/chip, no remat
    pu: MMTileSpec  # MM PU spec chosen for this stage's dominant MM


@dataclasses.dataclass(frozen=True)
class ExecutionPlan:
    """The derived accelerator instance for one (arch x mesh x shape)."""

    arch: str
    mesh_axes: tuple[tuple[str, int], ...]  # e.g. (("data",16),("model",16))
    mha: StagePlan
    ffn: StagePlan
    # C5: Independent-Linear — aggregate per-head QKV into one MM.
    fuse_qkv: bool
    # C4/P_ATB: attention-block parallel degree (heads consumed in parallel).
    p_atb: int
    # Head sharding degree over the model axis (0 = heads not sharded).
    head_shards: int
    # Activation checkpointing + gradient accumulation (Factor2' outcome).
    remat: bool
    microbatches: int
    # Embedding partition dim: "vocab" | "embed" | "replicated".
    embed_shard: str
    # MoE execution mode: "ep" (experts sharded) | "tp" (d_ff sharded) | "none".
    moe_mode: str
    # Sequence parallelism for long-context cells (batch < data axis).
    seq_shard: bool
    # MoE dispatch algorithm: "gshard" grouped-einsum (baseline) | "sort".
    moe_dispatch: str = "gshard"
    # TEMPORAL mode folds the model axis into data parallelism (FSDP): the
    # paper's "each PRG uses ALL compute resources in turn" — without this
    # the model-axis chips would duplicate work (16/17 of FLOPs wasted).
    dp_over_model: bool = False
    # ZeRO/FSDP hybrid: weights + optimizer state also sharded over `data`
    # (needed when 12B/param x params / model_axis exceeds HBM).
    zero_weights: bool = False
    # Megatron-style sequence parallelism: the residual stream (and thus every
    # remat-saved layer input) is sharded over `model` on the seq dim.
    seq_parallel_acts: bool = False
    # Pod-axis role: "data" (extra DP) or "pipeline" (multi-EDPU pipelining, C9).
    # "pipeline" routes launch/train.py through dist.pipeline.pipeline_forward:
    # layer-groups slice over the pod axis, microbatches flow stage-to-stage.
    pod_role: str = "data"
    # Gradient exchange wire format ("none" | "bf16" | "int8").  When set, the
    # train step swaps GSPMD's fp32 gradient all-reduce for the shard_map
    # dist.collectives.compressed_psum exchange (compression happens once, on
    # the wire); when the mesh cannot host that path the same mode falls back
    # to train/compression.py's accumulation-dtype quantization.
    grad_compression: str = "none"

    @property
    def model_axis(self) -> int:
        return dict(self.mesh_axes).get("model", 1)

    @property
    def data_axis(self) -> int:
        return dict(self.mesh_axes).get("data", 1)

    @property
    def pod_axis(self) -> int:
        return dict(self.mesh_axes).get("pod", 1)

    def mode_for(self, stage: str) -> str:
        """Parallel mode the dist sharder executes for a stage ("mha"|"ffn").

        When dp_over_model folds the model axis into data parallelism the
        whole network runs TEMPORAL regardless of per-stage feasibility —
        the model axis is occupied by batch and cannot also carry TP.
        """
        if self.dp_over_model:
            return TEMPORAL
        if stage == "mha":
            return self.mha.mode
        if stage == "ffn":
            return self.ffn.mode
        raise KeyError(f"unknown stage {stage!r}; expected 'mha' or 'ffn'")

    def describe(self) -> str:
        rows = [
            f"accelerator instance for {self.arch}",
            f"  mesh            : {dict(self.mesh_axes)} (pod role: {self.pod_role})",
            f"  MHA stage       : mode={self.mha.mode} factor1={self.mha.factor1:.3f} "
            f"factor2={self.mha.factor2_bytes/1e6:.1f}MB pu={self.mha.pu.name}"
            f"({self.mha.pu.block_m}x{self.mha.pu.block_n}x{self.mha.pu.block_k})",
            f"  FFN stage       : mode={self.ffn.mode} factor1={self.ffn.factor1:.3f} "
            f"factor2={self.ffn.factor2_bytes/1e6:.1f}MB pu={self.ffn.pu.name}"
            f"({self.ffn.pu.block_m}x{self.ffn.pu.block_n}x{self.ffn.pu.block_k})",
            f"  fuse_qkv (C5)   : {self.fuse_qkv}",
            f"  P_ATB (C4)      : {self.p_atb} (head_shards={self.head_shards})",
            f"  remat/microbatch: {self.remat}/{self.microbatches}",
            f"  embed shard     : {self.embed_shard}   moe: {self.moe_mode}"
            f"   seq_shard: {self.seq_shard}",
            f"  seq-parallel/SP : {self.seq_parallel_acts}"
            f"   grad wire: {self.grad_compression}",
        ]
        return "\n".join(rows)


def _largest_divisor_leq(n: int, cap: int) -> int:
    for d in range(min(n, cap), 0, -1):
        if n % d == 0 and cap % d == 0:
            return d
    return 1


def derive_plan(
    cfg,
    mesh_shape: Mapping[str, int],
    hw: HardwareSpec = DEFAULT_HARDWARE,
    *,
    batch: int = 8,
    seq_len: int = 2048,
    training: bool = True,
    fuse_qkv: Optional[bool] = None,
    force_mode: Optional[str] = None,
    pod_role: str = "data",
    dtype_bytes: int = 2,
    moe_dispatch: str = "gshard",
    seq_parallel: Optional[bool] = None,
    grad_compression: str = "none",
) -> ExecutionPlan:
    """Top-down derivation (paper §IV): hardware + model jointly decide."""
    ma = mesh_shape.get("model", 1)
    da = mesh_shape.get("data", 1)
    family = derive_pu_family(hw, dtype_bytes)

    # ---- Eq.5 Factor1 (MHA): LB MM scale / engine one-shot MM scale. -------
    lb_mm_volume = 4.0 * seq_len * cfg.d_model * cfg.n_heads * cfg.d_head
    engine_volume = float(ma) * family["LARGE"].block_m * family[
        "LARGE"
    ].block_n * family["LARGE"].block_k
    mha_factor1 = lb_mm_volume / engine_volume

    # ---- Eq.6 Factor1 (FFN). ------------------------------------------------
    ffn_volume = 2.0 * seq_len * cfg.d_model * cfg.d_ff
    ffn_factor1 = ffn_volume / engine_volume

    # ---- GSPMD divisibility (needed by Factor2' and the mode decision). ----
    heads_div = cfg.n_heads % ma == 0 and (cfg.n_kv_heads % ma == 0 or cfg.n_kv_heads < ma)
    ffn_shard_w = cfg.effective_ff_width()
    ffn_div = ffn_shard_w % ma == 0 and (ffn_shard_w // ma) >= hw.mxu_dim
    tp_feasible = heads_div and cfg.d_model % ma == 0

    # ---- Factor2': activation bytes per chip if nothing is rematerialized. --
    tokens = batch * seq_len
    tokens_per_chip = tokens / max(da, 1)
    width_frac = 1.0 / ma  # hidden sharded over model axis in SPATIAL mode
    qkv_width = (cfg.n_heads + 2 * cfg.n_kv_heads) * cfg.d_head
    mha_act = tokens_per_chip * (cfg.d_model + qkv_width + cfg.n_heads * cfg.d_head)
    ffn_act = tokens_per_chip * (cfg.d_model + 2 * cfg.effective_ff_width())
    mha_factor2 = int(mha_act * dtype_bytes * width_frac * cfg.n_layers)
    ffn_factor2 = int(ffn_act * dtype_bytes * width_frac * cfg.n_layers)
    # Attention probabilities (fp32) are the big saved residual without remat:
    # tokens_per_chip x kv-extent x heads x 4B per attention layer.
    attn_layers = sum(
        1 for i in range(cfg.n_layers) if cfg.layer_kind(i) in ("attn", "swa", "local")
    )
    eff_kv = min(seq_len, cfg.sliding_window or seq_len)
    probs = tokens_per_chip * eff_kv * cfg.n_heads * 4.0
    mha_factor2 += int(probs * attn_layers / (ma if tp_feasible else 1))

    # The batch can fold over the model axis (TEMPORAL -> FSDP, no duplicate
    # compute) only when it divides the full dp extent.
    can_fold = batch % max(da * ma, 1) == 0 and batch >= da

    def decide(factor1: float, factor2: int, feasible: bool) -> str:
        if force_mode:
            return force_mode
        return SPATIAL if feasible else TEMPORAL

    mha_mode = decide(mha_factor1, mha_factor2, tp_feasible)
    ffn_mode = decide(ffn_factor1, ffn_factor2, ffn_div and cfg.d_model % ma == 0)

    # Paper Eq.5/6 restored (§Perf iteration 6): when the model's MM scale
    # dwarfs the engine's one-shot scale (Factor1 >= PRG depth), the paper
    # picks mode (2) — serial, each PRG using ALL compute.  On TPU that is
    # FSDP with the model axis folded into DP.  Measured on
    # mistral-large/train_4k: collective 112s -> (see EXPERIMENTS §Perf).
    # My earlier "spatial always wins when divisible" deviation was wrong for
    # compute-huge dense models.  MoE keeps its spatial/EP FFN (expert
    # weights are consumed by few tokens each — gathering them all per layer
    # would not amortize).
    if (
        training
        and not cfg.is_moe
        and can_fold
        and force_mode is None
        and max(mha_factor1, ffn_factor1) >= PRG_MAX_PIPELINE_DEPTH
    ):
        mha_mode = TEMPORAL
        ffn_mode = TEMPORAL

    seq_shard = batch % max(da, 1) != 0 or batch < da
    dp_over_model = (
        mha_mode == TEMPORAL
        and ffn_mode == TEMPORAL
        and not seq_shard
        and batch % max(da * ma, 1) == 0
    )

    # ---- P_ATB (Eq.7/8): heads consumed in parallel per fused-QKV output. --
    head_shards = _largest_divisor_leq(cfg.n_heads, ma) if mha_mode == SPATIAL else 1
    if cfg.n_heads % max(head_shards, 1):
        head_shards = 1
    p_atb = max(1, cfg.n_heads // max(head_shards, 1))

    # ---- PU selection per stage (C2). ---------------------------------------
    mha_m = seq_len * batch // max(da, 1)
    mha_pu = pick_pu(mha_m, qkv_width // max(head_shards, 1), cfg.d_model, hw, dtype_bytes)
    ffn_pu = pick_pu(
        mha_m,
        max(ffn_shard_w // (ma if ffn_mode == SPATIAL else 1), hw.mxu_dim),
        cfg.d_model,
        hw,
        dtype_bytes,
    )

    # ---- Factor2' outcome: ZeRO weights + remat + microbatches. -------------
    # Optimizer state (bf16 params + fp32 m/v + grad ~ 12B/param when
    # training; just the bf16 weights when serving) sharded over the model
    # axis only can exceed HBM for 100B-class models: shard the complementary
    # weight dim over `data` too (ZeRO/FSDP hybrid; for decode the act
    # all-reduces at tiny batch are ~free, so 2-D weight sharding is pure win).
    bytes_per_param = 12.0 if training else float(dtype_bytes)
    param_bytes_model_only = cfg.param_count() * bytes_per_param / ma
    # Inference threshold is deliberately high (§Perf cell-3 iteration): 2-D
    # weight sharding at decode forces per-token weight all-gathers over
    # `data` (measured 70x step-time regression on mistral decode when
    # applied below need).  Only shard 2-D when weights would not otherwise
    # fit; the designed answer for capacity-tight serving is the int8
    # mm_pu path (the paper's own Int8 deployment mode).
    zero_weights = param_bytes_model_only > (0.35 if training else 1.0) * hw.hbm_bytes
    param_bytes = param_bytes_model_only / (da if zero_weights else 1)
    act_budget = max(hw.hbm_bytes - param_bytes, hw.hbm_bytes * 0.25)
    total_act = mha_factor2 + ffn_factor2
    remat = training and total_act > 0.25 * act_budget
    # §Perf iteration log: Megatron-SP via a pjit sharding constraint alone
    # was REFUTED twice on mistral-large (112s -> 144s collective at micro=2;
    # 935s at micro=16 — GSPMD thrashes between seq-sharded residuals and
    # gathered attention inputs).  Proper SP needs shard_map-manual
    # collectives — which models/transformer.sp_stack_forward now supplies
    # (ring-overlap gather-matmul + reduce-scatter; docs/ARCHITECTURE.md
    # §"Megatron-SP").  The flag therefore stays opt-in (``seq_parallel=``)
    # rather than auto-derived, and only engages on meshes/models the manual
    # path supports: every projection must column/row-shard evenly and the
    # sequence must split over the model axis.
    sp_feasible = (
        ma > 1
        and not cfg.is_moe
        and not cfg.enc_dec
        and all(k in ("attn", "swa", "local") for k in cfg.layer_pattern)
        and mha_mode == SPATIAL
        and ffn_mode == SPATIAL
        and cfg.n_heads % ma == 0
        and cfg.n_kv_heads % ma == 0
        and seq_len % ma == 0
        and cfg.effective_ff_width() % ma == 0
        and not seq_shard
        and not zero_weights  # manual ring assumes whole column/row shards
    )
    seq_parallel_acts = bool(seq_parallel) and sp_feasible
    if seq_parallel_acts:
        # The manual ring needs per-projection column shards: a fused
        # (q|k|v) column split would hand each device a q/k/v mix.
        fuse_qkv = False
    # remat-saved layer inputs.  NOTE §Perf iteration log: crediting SP with
    # a /model_axis here (and so cutting microbatches 16->2) was REFUTED on
    # mistral-large — per-microbatch transients grew 8x and temp went 26->35
    # GB.  The SP constraint stays, the memory credit does not.
    saved_per_layer = tokens_per_chip * cfg.d_model * dtype_bytes
    resid = saved_per_layer * cfg.n_layers
    # per-microbatch global batch must stay divisible by the DP extent,
    # otherwise GSPMD replicates tokens (measured: 21x FLOPs waste).
    dp_total = da * (ma if dp_over_model else 1)
    micro_cap = max(1, batch // max(dp_total, 1))
    microbatches = 1
    while (
        training
        and resid / microbatches > 0.5 * act_budget
        and microbatches * 2 <= micro_cap
        and batch % (microbatches * 2) == 0
    ):
        microbatches *= 2

    # Pipeline pods need the pipe *full*: with M microbatches over S stages
    # the GPipe bubble is (S-1)/(M+S-1) (dist.pipeline.bubble_fraction), so
    # raise M to the largest batch divisor <= 4*S — 4x stages pushes the
    # bubble under ~1/5 while per-microbatch memory stays a plan-visible
    # trade (docs/ARCHITECTURE.md §"Pod axis").
    pa = mesh_shape.get("pod", 1)
    if training and pod_role == "pipeline" and pa > 1:
        for cand in range(min(batch, 4 * pa), microbatches, -1):
            # the microbatch must still fold over the data axis — token
            # replication across DP replicas (measured 21x FLOPs waste)
            # is worse than any bubble, so no fallback: an unfillable
            # pipe fails loudly in check_pipeline_supported instead.
            if batch % cand == 0 and cand >= pa and (batch // cand) % max(da, 1) == 0:
                microbatches = cand
                break

    # ---- Embedding + MoE + sequence sharding. -------------------------------
    if cfg.vocab_size % ma == 0:
        embed_shard = "vocab"
    elif cfg.d_model % ma == 0:
        embed_shard = "embed"
    else:
        embed_shard = "replicated"
    if cfg.n_experts > 1:
        if cfg.n_experts % ma == 0:
            moe_mode = "ep"
        elif cfg.moe_d_ff % ma == 0 and cfg.moe_d_ff // ma >= hw.mxu_dim:
            moe_mode = "tp"
        else:
            moe_mode = "none"
    else:
        moe_mode = "none"
    # C5 (Independent-Linear): fused QKV everywhere.  §Perf iteration log:
    # the hypothesis that a fused (q+2kv) column shard misaligned with GQA
    # boundaries causes resharding all-reduces was REFUTED on
    # mistral-large/train_4k — splitting the projections replaced XLA's cheap
    # collective-permutes (341 GB) with all-reduces (+567 GB): keep fused.
    if fuse_qkv is None:
        fuse_qkv = cfg.fused_qkv_ok()

    return ExecutionPlan(
        arch=cfg.name,
        mesh_axes=tuple(sorted(mesh_shape.items())),
        mha=StagePlan(mha_mode, mha_factor1, mha_factor2, mha_pu),
        ffn=StagePlan(ffn_mode, ffn_factor1, ffn_factor2, ffn_pu),
        fuse_qkv=fuse_qkv,
        p_atb=p_atb,
        head_shards=head_shards,
        remat=remat,
        microbatches=max(1, microbatches),
        embed_shard=embed_shard,
        moe_mode=moe_mode,
        moe_dispatch=moe_dispatch,
        seq_shard=seq_shard,
        dp_over_model=dp_over_model,
        zero_weights=zero_weights,
        seq_parallel_acts=seq_parallel_acts,
        pod_role=pod_role,
        grad_compression=grad_compression,
    )


# ---------------------------------------------------------------------------
# Serve mode: the plan layer for the continuous-batching engine.
#
# CAT is an *inference* framework — the same top-down contract that decides
# the training mesh (hardware + model jointly constrain) decides the serving
# knobs: how many decode slots run concurrently, how the paged KV cache is
# blocked, and what dtype the KV pages hold.  `serve/engine.py` executes
# these decisions; `launch/serve.py` and the dry-run surface them.
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class ServePlan:
    """Derived serving configuration for one (arch x mesh x hardware).

    Frozen + hashable so it can ride as a static argument of the jitted
    prefill/decode steps exactly like :class:`ExecutionPlan`.
    """

    arch: str
    # Concurrent decode slots (the engine's static decode batch).
    decode_batch: int
    # Paged KV cache geometry: tokens per block / pool blocks per attention
    # layer / table width per request.  Block 0 is the trash block (writes
    # from idle slots land there), so the allocatable pool is n_blocks - 1.
    block_size: int
    n_blocks: int
    max_blocks_per_seq: int
    # KV page dtype: "bf16" | "int8" | "fp32" (int8 reuses
    # train/compression.quantize on a per-token, per-head grid).
    kv_dtype: str
    # Tokens per prefill chunk (derivation target for the mixed-slab width).
    prefill_chunk: int
    # Serving context bound: block tables cover exactly this many positions.
    max_seq_len: int
    # The most query rows one slot takes in a step (decode uses 1, a
    # speculating slot gamma+1, a prefill chunk up to all of them).  The
    # dense layers run the step's packed tokens (``step_tokens``), and the
    # paged kernel gets them back as a (B, W) slab of this width.
    mixed_slab_width: int = 1
    # KV pages streamed into one VMEM tile per kernel grid step (the fused
    # paged-attention kernel's tile height), from the VMEM budget.
    pages_per_tile: int = 1
    # Attention engine of the unified step: the fused Pallas paged-attention
    # kernel (True) vs the dense gather-then-attend fallback (False).  The
    # roofline charges the fallback its gather bytes, so this knob feeds the
    # decode-batch derivation too.
    fused_attention: bool = True
    # Rolled on-device decode loop: max decode iterations per host dispatch
    # (K).  One host round-trip costs ``hw.dispatch_overhead_s`` regardless
    # of how much work it launches — the CAT/EA4RCA communication-avoiding
    # argument — so at small decode batch (steps are short, overhead is a
    # large fraction) the engine rolls K sampling+repack+length-advance
    # iterations into ONE ``lax.while_loop`` dispatch.  Derived so the
    # dispatch overhead amortizes below ~10% of the rolled span; 1 disables
    # rolling (every step is a host round-trip, the pre-rolled contract).
    # The scheduler still chooses the *actual* K per dispatch from the
    # event horizon (next admission/prefill/speculation/growth boundary),
    # bounded above by this plan cap.
    rolled_steps: int = 1
    # Speculative decoding: draft depth per decode slot (gamma).  A
    # speculating slot submits spec_len drafted tokens + its real one as
    # gamma+1 slab rows — mechanically a prefill chunk — and the host keeps
    # the longest draft prefix matching the step's greedy argmax.  0 = off.
    # Derived from the roofline's compute-vs-bandwidth slack: decode is
    # bandwidth-bound, so the MXU has (machine_balance / decode_batch) rows
    # of free compute per slot before verification itself would go
    # compute-bound (then gamma stays 0).  Always <= mixed_slab_width - 1.
    spec_len: int = 0
    # Draft source label: "none" | "ngram" (prompt-lookup self-drafting) |
    # a config name (model drafting, e.g. "smollm-135m").  The engine takes
    # the actual DraftSource object; the plan records the decision.
    draft: str = "none"
    # Copy-on-write prefix sharing: the scheduler keeps a radix index over
    # resident token prefixes and admits prefix-hit requests with shared
    # (refcounted) blocks + only the divergent tail as prefill.  Greedy
    # outputs are byte-identical either way (KV pages are a pure function
    # of the token prefix); the knob exists for A/B accounting and as the
    # escape hatch, not because sharing changes results.
    prefix_sharing: bool = True
    # Fleet-default TTFT target (ms) that shaped this plan, when one did:
    # the derivation widens the mixed slab so a typical prompt prefils
    # within the target and reins in gamma (draft rows compete with prompt
    # chunks for slab width).  Per-request targets on ``Request.slo_ttft_ms``
    # drive the scheduler's runtime chunk sizing; this field records the
    # planning-time decision.  None = throughput-shaped plan.
    slo_ttft_ms: Optional[float] = None
    # --- robustness knobs (fault-tolerance ladder; see docs/ROBUSTNESS.md) ---
    # Fleet-default wall-clock deadline (ms from submit) after which a
    # request is cancelled and its blocks/radix refs released; per-request
    # ``Request.deadline_ms`` overrides.  None = no deadline.
    deadline_ms: Optional[float] = None
    # Transient-dispatch retries per ladder rung before stepping down
    # rolled-K -> K=1 mixed -> eager gather fallback (then giving up).
    retry_limit: int = 3
    # Base for the exponential retry backoff: sleep backoff * 2^(attempt-1)
    # seconds (capped at 0.25 s) between retries.  Tests set it to 0.
    retry_backoff_s: float = 0.001
    # Consecutive healthy dispatches before the engine climbs one rung
    # back up the ladder.
    ladder_recovery: int = 32
    # Iterations an *arrived* request may sit admission-blocked (pool or
    # slot saturation) before it is shed with a retry-after hint, instead
    # of livelocking behind eviction.
    admission_patience: int = 128
    # Consecutive no-progress engine iterations (no tokens, no admission,
    # no completion) before ``run()`` raises StallError carrying health().
    stall_limit: int = 256
    # Consecutive quarantined (non-finite logits) steps for one slot before
    # the request is cancelled as poisoned rather than replayed again.
    quarantine_limit: int = 8
    # Diagnostics (logged + dryrun records).
    kv_bytes_per_token: int = 0
    hbm_kv_budget_bytes: int = 0

    @property
    def step_tokens(self) -> int:
        """T, the static length of the mixed step's packed token stream:
        the smallest multiple of 128 that holds every running slot's rows
        (``decode_batch * (1 + spec_len)``) and four prefill chunks of
        ``mixed_slab_width``, and never more than the whole (B, W) slab,
        which no step can outgrow.  Four chunks, not two: a step pays a
        fixed cost (the weight stream, the pool's per-layer slices, the
        paged kernel over every running slot) that dwarfs a row's share of
        the dense layers, so a burst of prompts — a server filling its
        slots, arrivals that bunch — clears in half the steps for a few
        per cent more on every step.  A property of B, W and gamma, so a
        plan rebuilt with ``dataclasses.replace`` keeps a budget that fits
        it."""
        B, W = self.decode_batch, self.mixed_slab_width
        need = B * (1 + self.spec_len) + 4 * W
        return min(-(-need // 128) * 128, B * W)

    @property
    def max_concurrency(self) -> int:
        """Requests the block pool can hold at full context length."""
        return (self.n_blocks - 1) // self.max_blocks_per_seq

    def describe(self) -> str:
        return (
            f"serve plan for {self.arch}: decode_batch={self.decode_batch} "
            f"block_size={self.block_size} n_blocks={self.n_blocks} "
            f"kv_dtype={self.kv_dtype} prefill_chunk={self.prefill_chunk} "
            f"slab={self.mixed_slab_width} step_tokens={self.step_tokens} "
            f"pages/tile={self.pages_per_tile} "
            f"fused={self.fused_attention} rolled_steps={self.rolled_steps} "
            f"spec_len={self.spec_len} "
            f"draft={self.draft} prefix_sharing={self.prefix_sharing} "
            f"slo_ttft_ms={self.slo_ttft_ms} max_seq={self.max_seq_len} "
            f"kv_bytes/token={self.kv_bytes_per_token}"
        )

    def to_record(self) -> dict:
        """Flat dict for dryrun / benchmark JSON records."""
        return {
            "decode_batch": self.decode_batch,
            "block_size": self.block_size,
            "n_blocks": self.n_blocks,
            "max_blocks_per_seq": self.max_blocks_per_seq,
            "kv_dtype": self.kv_dtype,
            "prefill_chunk": self.prefill_chunk,
            "mixed_slab_width": self.mixed_slab_width,
            "step_tokens": self.step_tokens,
            "pages_per_tile": self.pages_per_tile,
            "fused_attention": self.fused_attention,
            "rolled_steps": self.rolled_steps,
            "spec_len": self.spec_len,
            "draft": self.draft,
            "prefix_sharing": self.prefix_sharing,
            "slo_ttft_ms": self.slo_ttft_ms,
            "deadline_ms": self.deadline_ms,
            "retry_limit": self.retry_limit,
            "retry_backoff_s": self.retry_backoff_s,
            "ladder_recovery": self.ladder_recovery,
            "admission_patience": self.admission_patience,
            "stall_limit": self.stall_limit,
            "quarantine_limit": self.quarantine_limit,
            "max_seq_len": self.max_seq_len,
            "kv_bytes_per_token": self.kv_bytes_per_token,
        }


def serve_feasible(cfg) -> tuple[bool, str]:
    """Can the continuous-batching engine host this arch?

    The paged path needs per-slot positions (rope/none) and a pure-attention
    layer stack (recurrent state is O(1)/request and needs no paging; those
    archs stay on the eager ``greedy_generate`` path for now).
    """
    if cfg.enc_dec or cfg.frontend != "none":
        return False, "enc-dec/frontend archs keep non-stack state"
    if not all(k in ("attn", "swa", "local") for k in cfg.layer_pattern):
        return False, f"layer pattern {cfg.layer_pattern} has recurrent blocks"
    if cfg.pos_embedding not in ("rope", "none"):
        return False, f"pos_embedding={cfg.pos_embedding} needs scalar offsets"
    if not cfg.causal or cfg.encoder_only:
        return False, "serving needs a causal decoder"
    return True, ""


def _pow2_floor(n: int) -> int:
    p = 1
    while p * 2 <= n:
        p *= 2
    return p


_DTYPE_BYTES = {"fp32": 4, "bf16": 2, "int8": 1}
# The dtype the server holds its weights (and so its activations) in.  The
# KV pool's HBM budget and the kernel's VMEM footprint are sized against
# it, and the serving launchers initialize weights in it.
SERVE_WEIGHT_DTYPE = "float32"
# HBM XLA:TPU keeps from a program: it compiles against 15.75 of a v5e's
# 16 GiB.
_XLA_HBM_RESERVE = 2**28


def int8_scale_lanes(n_kv_heads: int, block_size: int) -> int:
    """Lanes of one page's int8 scale row: ``block_size * n_kv_heads`` fp32
    scales (token-major), rounded up to whole 128-lane vregs so the fused
    kernel can fetch a page's scales with one aligned DMA."""
    return -(-block_size * n_kv_heads // 128) * 128


def largest_divisor_of(n: int, cap: int) -> int:
    """Largest divisor of ``n`` that is <= ``cap`` (>= 1).  Unlike
    :func:`_largest_divisor_leq` it puts no divisibility demand on ``cap``."""
    for d in range(min(n, max(cap, 1)), 0, -1):
        if n % d == 0:
            return d
    return 1


def derive_serve_plan(
    cfg,
    mesh_shape: Mapping[str, int],
    hw: HardwareSpec = DEFAULT_HARDWARE,
    *,
    max_seq_len: int = 2048,
    decode_batch: Optional[int] = None,
    block_size: Optional[int] = None,
    kv_dtype: Optional[str] = None,
    prefill_chunk: Optional[int] = None,
    mixed_slab_width: Optional[int] = None,
    pages_per_tile: Optional[int] = None,
    fused_attention: bool = True,
    rolled_steps: Optional[int] = None,
    spec_len: Optional[int] = None,
    draft: str = "none",
    slack_blocks: int = 0,
    oversubscribe: float = 1.0,
    prefix_sharing: bool = True,
    slo_ttft_ms: Optional[float] = None,
    typical_prompt_len: Optional[int] = None,
    deadline_ms: Optional[float] = None,
    retry_limit: int = 3,
    retry_backoff_s: float = 0.001,
    ladder_recovery: int = 32,
    admission_patience: int = 128,
    stall_limit: int = 256,
    quarantine_limit: int = 8,
) -> ServePlan:
    """Pick decode batch / block size / KV dtype from the roofline model.

    * **decode batch** — decode is weight-streaming-bound; batching tokens
      amortizes the weight read until compute catches up at the machine
      balance point (Eq.4 analog): B* ~= machine_balance x bytes/param / 2.
      Capped by the HBM KV budget at full context.  With the fused
      paged-attention kernel each slot's HBM traffic is just its own pages
      read once; the gather fallback instead writes *and* re-reads a dense
      ``max_seq_len``-long cache per slot per step, so its per-slot byte tax
      (2 x ``max_seq_len`` x kv_bytes/token) stops the batch from amortizing
      the weight stream long before the balance point — the fallback batch
      is additionally capped at weight_bytes / gather_tax.  The fused
      kernel's plan simply drops that term.
    * **KV dtype** — bf16 unless the bf16 pool cannot hold the
      roofline-preferred batch at ``max_seq_len``; then the paper's Int8
      deployment grid halves the page bytes (C2's precision knob applied to
      the cache instead of the weights).
    * **block size** — one MXU sublane tile (``mxu_dim // 8``) so a page
      feeds the MM PU without re-tiling; never wider than the context.
    * **mixed-slab width** — the most query rows one slot takes in the
      unified step; defaults to ``prefill_chunk`` (prefill keeps its
      compute-bound chunk).  The step's dense layers run a packed stream
      of ``ServePlan.step_tokens`` rows, derived from the batch, this width
      and gamma, so a decode slot costs one row, not W.
    * **pages per kernel tile** — the kernel sweeps a slot's context in
      tiles of ``pages_per_tile * block_size`` keys, and each tile costs a
      fixed share of loop, DMA issue and wait on top of its keys' work.
      The tile is the smallest block-table divisor of at least
      ``hw.mxu_dim`` keys (a tile then fills the score arrays' 128 lanes
      and the depth of the p·V contraction), never past the largest
      divisor whose whole kernel footprint
      (``kernels/paged_attention/kernel.vmem_bytes``: q and output blocks,
      the softmax carry, the score arrays, the double-buffered page tiles
      and their in-kernel copies) fits half the chip's VMEM.  The kernel
      asks Mosaic for that footprint and a quarter more
      (``kernel.vmem_limit``), so half leaves the limit well inside the
      chip's VMEM (64 of 128 MiB on v5e).
    * **rolled decode steps (K cap)** — how many decode iterations one host
      dispatch should carry.  A dispatch costs ``hw.dispatch_overhead_s``
      no matter how much it launches, while one decode step is
      weight-stream-bound (~ weight_bytes / hbm_bandwidth); the overhead
      fraction is therefore ``overhead / (K x step)``.  K is the smallest
      power of two holding that fraction under ~10% (1 when a single step
      already amortizes it — big models — and capped at 32: past that the
      host loses admission/completion responsiveness for < 0.4% more).  A
      TTFT target additionally caps K so a rolled span cannot blockade an
      arriving prompt past ~a quarter of its budget.
    * **speculative draft depth (gamma)** — the joint-constraint answer to
      "how many draft rows per slot can verification absorb for free":
      decode at batch B is bandwidth-bound (B below the machine balance
      point), so one weight stream amortizes ``machine_balance / B`` query
      rows per slot before the MXU goes compute-bound.  gamma+1 must stay
      within that slack *and* within the slab width, else gamma drops to 0
      (verification must never slow the step it is trying to speed up).
      Only derived when a ``draft`` source is named; explicit ``spec_len``
      overrides (still clamped to the slab).
    * **SLO feedback** — a fleet TTFT target (``slo_ttft_ms``) feeds back
      into the slab and gamma: steps are weight-stream-bound (>=
      weight_bytes / hbm_bandwidth each), so the target fixes a step
      budget, the slab widens until ``typical_prompt_len`` prefils inside
      it, and gamma is reined in to ``slack // 2 - 1`` (draft rows compete
      with prompt chunks for slab width).  Per-request targets
      (``Request.slo_ttft_ms``) additionally drive runtime chunk sizing in
      the scheduler against *measured* step times.

    The KV pool gets the HBM the weights leave free at
    ``SERVE_WEIGHT_DTYPE``, the width the server holds them in.  (The decode
    roofline above still prices a bf16 weight stream.)

    ``oversubscribe`` scales the block pool relative to the worst case
    (every slot at ``max_seq_len``).  At the default 1.0 the pool can host
    every admitted request to full context, so derived plans are
    *eviction-free by construction* — the scheduler's eviction path only
    engages when an operator oversubscribes (< 1.0) to trade KV memory for
    admission capacity, betting that most requests stop early.
    """
    ok, reason = serve_feasible(cfg)
    if not ok:
        raise ValueError(f"no serve plan for {cfg.name}: {reason}")
    ma = mesh_shape.get("model", 1)
    n_attn = sum(
        1 for i in range(cfg.n_layers) if cfg.layer_kind(i) in ("attn", "swa", "local")
    )
    weight_bytes = cfg.param_count() * 2.0 / max(ma, 1)  # bf16 roofline
    if block_size is None:
        block_size = max(8, hw.mxu_dim // 8)
    block_size = min(block_size, max_seq_len)
    if prefill_chunk is None:
        prefill_chunk = min(max_seq_len, max(block_size, 256))
    if mixed_slab_width is None:
        mixed_slab_width = prefill_chunk
    if slo_ttft_ms is not None:
        # TTFT feedback (same joint-constraint style as the decode batch):
        # decode steps are weight-stream-bound, so one step costs at least
        # weight_bytes / hbm_bandwidth — that bounds how many steps fit in
        # the TTFT budget, and a typical prompt must prefill within them.
        # Widen the slab until it does (never narrow a wider request).
        est_step_s = weight_bytes / max(hw.hbm_bandwidth, 1.0)
        steps_budget = max(1, int((slo_ttft_ms / 1e3) / max(est_step_s, 1e-12)))
        need = -(-int(typical_prompt_len or max_seq_len) // steps_budget)
        mixed_slab_width = max(int(mixed_slab_width), need)
    mixed_slab_width = max(1, min(mixed_slab_width, max_seq_len))

    # HBM left for the KV pool and the step's activations: what XLA:TPU
    # leaves a program, less the weights as served — plus, for fp32
    # weights, the bf16 copies XLA:TPU feeds the MXU (default-precision
    # f32 matmuls run on bf16 operands; the converts are hoisted out of the
    # layer loop, 2 B/param).  Both were read off ``memory_analysis()`` of
    # the unified step compiled at qwen3-1.7b width for a v5e.
    act_bytes = np.dtype(SERVE_WEIGHT_DTYPE).itemsize
    wb = act_bytes + (2 if SERVE_WEIGHT_DTYPE == "float32" else 0)
    held_weight_bytes = cfg.param_count() * wb / max(ma, 1)
    usable = hw.hbm_bytes - _XLA_HBM_RESERVE
    kv_budget = int(max(usable - held_weight_bytes, 0.1 * hw.hbm_bytes))
    # one slab row's live activations (FFN, fused QKV and residual widths;
    # ~87 KB measured for qwen3-1.7b in fp32 against 106 KB here)
    row_bytes = act_bytes * (
        3 * cfg.effective_ff_width()
        + (cfg.n_heads + 2 * cfg.n_kv_heads) * cfg.d_head
        + 2 * cfg.d_model
    )

    def per_token(dtype: str) -> int:
        tok = n_attn * 2 * cfg.n_kv_heads * cfg.d_head * _DTYPE_BYTES[dtype]
        if dtype == "int8":  # fp32 scale rows, padded to whole vregs
            tok += n_attn * 2 * 4 * int8_scale_lanes(cfg.n_kv_heads, block_size) // block_size
        return tok

    def slot_bytes(dtype: str) -> int:  # a slot's pages at full context + its slab
        return max_seq_len * per_token(dtype) + mixed_slab_width * row_bytes

    # Roofline batch: tokens per step needed to amortize the weight stream.
    # A degenerate device with no off-chip bandwidth reports an infinite
    # machine balance (nothing amortizes); clamp so the int() below is total
    # — the KV-capacity cap then decides the batch alone.
    balance = min(hw.machine_balance_bf16, 2.0**20)
    ridge = max(1, int(balance * 2.0 / (2.0 * max(ma, 1))))
    if kv_dtype is None:
        want = decode_batch or _pow2_floor(ridge)
        kv_dtype = "bf16" if want * slot_bytes("bf16") <= kv_budget else "int8"
    kv_tok = per_token(kv_dtype)
    cap = max(1, kv_budget // max(slot_bytes(kv_dtype), 1))
    if not fused_attention:
        # Gather-bytes term (fallback only): every slot drags a dense
        # write+read of its full-context cache through HBM each step.
        gather_tax = 2.0 * max_seq_len * kv_tok
        cap = max(1, min(cap, int(weight_bytes / max(gather_tax, 1.0))))
    if decode_batch is None:
        decode_batch = max(1, min(_pow2_floor(ridge), _pow2_floor(cap)))
    max_blocks_per_seq = -(-max_seq_len // block_size)  # ceil
    pool = max(max_blocks_per_seq, int(decode_batch * max_blocks_per_seq * oversubscribe))
    n_blocks = 1 + pool + slack_blocks  # +1: block 0 is trash
    if pages_per_tile is None:
        from repro.kernels.paged_attention.kernel import vmem_bytes

        def footprint(ppt: int) -> int:
            return vmem_bytes(
                kv_dtype=kv_dtype,
                q_itemsize=act_bytes,
                kv_heads=cfg.n_kv_heads,
                rows=(cfg.n_heads // cfg.n_kv_heads) * int(mixed_slab_width),
                head_dim=cfg.d_head,
                block_size=block_size,
                pages_per_tile=ppt,
            )

        fits = [
            ppt for ppt in range(1, max_blocks_per_seq + 1)
            if max_blocks_per_seq % ppt == 0 and footprint(ppt) <= hw.vmem_bytes // 2
        ] or [1]
        pages_per_tile = next(
            (ppt for ppt in fits if ppt * block_size >= hw.mxu_dim), fits[-1]
        )
    if rolled_steps is None:
        # Dispatch-overhead slack: one decode step streams the weights once
        # (est_step_s); the host round-trip costs dispatch_overhead_s on
        # top.  Roll K steps per dispatch until the overhead fraction
        # overhead / (K * step) drops under ~10%.
        est_step_s = weight_bytes / max(hw.hbm_bandwidth, 1.0)
        rolled_steps = 1
        while (
            hw.dispatch_overhead_s > 0.1 * rolled_steps * max(est_step_s, 1e-12)
            and rolled_steps < 32
        ):
            rolled_steps *= 2
        if slo_ttft_ms is not None:
            # an arriving prompt waits out the in-flight rolled span before
            # its first prefill chunk: keep that wait under ~1/4 of the
            # TTFT budget so rolling never blows the very target the plan
            # was shaped for
            step_budget = max(
                1, int((slo_ttft_ms / 4e3) / max(est_step_s, 1e-12))
            )
            rolled_steps = min(rolled_steps, _pow2_floor(step_budget))
    rolled_steps = max(1, int(rolled_steps))
    if spec_len is None:
        if draft == "none":
            spec_len = 0
        else:
            # Compute slack per decode slot: the weight stream takes
            # weight_bytes / bw while one verified row costs
            # decode_batch * 2P/ma flops across the batch — both scale the
            # same way with TP, so slack rows/slot = machine_balance / B.
            # gamma+1 <= slack keeps verification bandwidth-bound; the -1
            # converts rows to drafts, and the cap of 8 bounds the verify
            # logits width (diminishing returns far before the slab does).
            slack = min(hw.machine_balance_bf16, 2.0**20) / max(int(decode_batch), 1)
            spec_len = max(0, min(int(slack) - 1, 8))
    if slo_ttft_ms is not None:
        # Under a TTFT target draft rows compete with prompt chunks for the
        # slab and lengthen the very steps the target budgets, so gamma only
        # keeps the slack it can *halve*: rein it in to slack//2 - 1 (0 when
        # the roofline slack is thin).
        slack = min(hw.machine_balance_bf16, 2.0**20) / max(int(decode_batch), 1)
        spec_len = min(int(spec_len), max(0, int(slack) // 2 - 1))
    spec_len = max(0, min(int(spec_len), int(mixed_slab_width) - 1))
    return ServePlan(
        arch=cfg.name,
        decode_batch=int(decode_batch),
        block_size=int(block_size),
        n_blocks=int(n_blocks),
        max_blocks_per_seq=int(max_blocks_per_seq),
        kv_dtype=kv_dtype,
        prefill_chunk=int(prefill_chunk),
        mixed_slab_width=int(mixed_slab_width),
        pages_per_tile=int(pages_per_tile),
        fused_attention=bool(fused_attention),
        rolled_steps=int(rolled_steps),
        spec_len=int(spec_len),
        draft=str(draft),
        prefix_sharing=bool(prefix_sharing),
        slo_ttft_ms=None if slo_ttft_ms is None else float(slo_ttft_ms),
        deadline_ms=None if deadline_ms is None else float(deadline_ms),
        retry_limit=int(retry_limit),
        retry_backoff_s=float(retry_backoff_s),
        ladder_recovery=int(ladder_recovery),
        admission_patience=int(admission_patience),
        stall_limit=int(stall_limit),
        quarantine_limit=int(quarantine_limit),
        max_seq_len=int(max_seq_len),
        kv_bytes_per_token=int(kv_tok),
        hbm_kv_budget_bytes=kv_budget,
    )


# ---------------------------------------------------------------------------
# Paper §V.B design case, on the paper's own hardware numbers.
# ---------------------------------------------------------------------------
def design_case_vck5000(seq_len: int = 256, d_model: int = 768, d_ff: int = 3072,
                        n_heads: int = 12) -> dict:
    """Reproduce the BERT-Base walk-through: Factor1 ~= 1.5, Factor2 ~= 7.56 MB,
    P_ATB = 4, fully-pipelined mode selected (paper §V.B)."""
    plio_aie, mmsz, total_aie = 4, 64, 400
    engine = (total_aie // plio_aie**2) * (plio_aie * mmsz) ** 3
    factor1 = 4 * seq_len * d_model**2 / engine
    d_head = d_model // n_heads
    buf = (
        seq_len * 256 * 3  # QKV LB output cache (int8 paper accounting)
        + seq_len * d_head * 4 * 4  # ATB in/out cache
        + 128 * seq_len * 4  # ATB attention cache
        + seq_len * 256 * 4  # ATB KV cache
        + seq_len * d_model + seq_len * 256  # Proj LB in/out
        + d_model * d_model * 4 + d_model * d_ff * 2  # weight cache
    )
    p_atb = 256 // d_head  # QKV LB emits 256-wide tiles; one head needs d_head
    mode = (
        SPATIAL
        if factor1 < PRG_MAX_PIPELINE_DEPTH and buf <= VCK5000.vmem_bytes
        else TEMPORAL
    )
    return {
        "factor1": factor1,
        "factor2_bytes": buf,
        "factor2_mb": buf / 2**20,
        "p_atb": p_atb,
        "mode": mode,
        "prg_max_pipeline_depth": PRG_MAX_PIPELINE_DEPTH,
        "buffer_budget_mb": VCK5000.vmem_bytes / 2**20,
    }
