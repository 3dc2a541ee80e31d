"""Serving: the continuous-batching engine + the eager prefill/decode steps.

Two layers:

* ``make_prefill_step`` / ``make_decode_step`` / ``greedy_generate`` — the
  eager whole-batch path (dense cache, every request in lockstep).  The
  dry-run lowers these for the decode_32k / long_500k / prefill_32k cells
  and non-attention archs (RWKV/RG-LRU/enc-dec) serve through it.
* ``ServingEngine`` — continuous batching over the paged KV cache
  (``models/cache.init_paged_cache``) with at most TWO static-shape jitted
  device programs: the unified mixed prefill/decode step, plus (when the
  plan's ``rolled_steps`` > 1) the rolled decode loop that runs K decode
  iterations per dispatch.  The mixed step runs a packed stream of
  ``ServePlan.step_tokens`` tokens, each tagged with its slot and position
  — a decode slot contributes 1, a prefill slot up to
  ``mixed_slab_width`` (its next prompt chunk), and the rows past the live
  tokens are padding that writes to the trash block — so prefilling new
  requests rides in the same step as the decode batch, and the dense
  layers run on the live tokens, not on a (B, W) slab.  Attention gathers
  q into the (B, W) slab of the fused Pallas paged-attention kernel
  (``kernels/paged_attention``), which consumes the block table directly,
  and gathers its output back; the dense gather fallback only remains for
  model-sharded meshes (GSPMD cannot partition the kernel yet) and as the
  oracle.  Scheduling policy lives host-side in ``serve/scheduler.py``; the
  knobs (decode batch, block size, KV dtype, slab width, token budget,
  pages per VMEM tile) come from ``core/plan.derive_serve_plan``.
"""
from __future__ import annotations

import time
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.core.hardware import DEFAULT_HARDWARE, HardwareSpec, resolve_hardware
from repro.core.plan import ExecutionPlan, ServePlan, largest_divisor_of, serve_feasible
from repro.kernels.paged_attention.kernel import live_tiles, rows_computed
from repro.models.cache import (
    cache_from_prefill,
    init_paged_cache,
    paged_copy_block,
)
from repro.models.transformer import forward, logits_fn, slab_index
from repro.obs import Observability, Phases
from repro.serve.faults import (
    LADDER,
    SALTS,
    FaultInjector,
    LadderExhausted,
    StallError,
    TransientDeviceError,
)
from repro.serve.scheduler import DONE, WAITING, Request, Scheduler

PyTree = Any
Identity = lambda x, name=None: x


def make_prefill_step(cfg: ArchConfig, plan: ExecutionPlan, shard: Callable = Identity):
    def prefill_step(params, batch):
        x, pc, _ = forward(
            params, batch, cfg=cfg, plan=plan, collect_cache=True, shard=shard
        )
        logits = logits_fn(params, x[:, -1:], cfg)
        return logits, pc

    return prefill_step


def make_decode_step(cfg: ArchConfig, plan: ExecutionPlan, shard: Callable = Identity):
    def decode_step(params, token, cache):
        x, new_cache, _ = forward(
            params, {"tokens": token}, cfg=cfg, plan=plan, cache=cache, shard=shard
        )
        logits = logits_fn(params, x, cfg)
        return logits, new_cache

    return decode_step


def greedy_generate(
    params: PyTree,
    cfg: ArchConfig,
    plan: ExecutionPlan,
    batch: dict,
    n_steps: int,
    cache_len: int,
    shard: Callable = Identity,
    cache_dtype=jnp.bfloat16,
):
    """Eager helper for the examples/tests (prefill then greedy decode).

    ``shard`` is a ``Shardings.constrain``-style callable; the default keeps
    single-device behaviour unchanged."""
    prefill = make_prefill_step(cfg, plan, shard=shard)
    decode = jax.jit(make_decode_step(cfg, plan, shard=shard))
    logits, pc = prefill(params, batch)
    cache = cache_from_prefill(cfg, plan, pc, cache_len, dtype=cache_dtype)
    tok = jnp.argmax(logits[:, -1], axis=-1)[:, None]
    out = [tok]
    for _ in range(n_steps - 1):
        logits, cache = decode(params, tok, cache)
        tok = jnp.argmax(logits[:, -1], axis=-1)[:, None]
        out.append(tok)
    return jnp.concatenate(out, axis=1)


# ---------------------------------------------------------------------------
# Continuous batching
# ---------------------------------------------------------------------------
def make_mixed_step(
    cfg: ArchConfig,
    plan: ExecutionPlan,
    serve: ServePlan,
    *,
    fused: bool,
    shard: Callable = Identity,
    spec_width: int = 1,
    trace: Optional[dict] = None,
    trace_key: str = "step",
):
    """Build the ONE jitted unified mixed prefill/decode step.

    ``step(params, pools, stream (3, T), tables (B, MB), lens (B,), q_lens
    (B,), poison (B,))`` returns ``(tok, vtok, finite, pools)``.  The step
    runs a packed stream of ``T = serve.step_tokens`` tokens, whose rows are
    ``tokens``, ``slot`` and ``pos`` (``serve/scheduler.Slab``): token t is
    slot ``slot[t]``'s token at absolute position ``pos[t]``, slot b's
    ``q_lens[b]`` tokens sit at positions ``lens[b]`` on, and rows past the
    live tokens are padding (``pos = -1``; their KV goes to the trash
    block).  The dense layers run on the T rows only; attention takes them
    through the paged kernel's (B, W) slab
    (``models/transformer.slab_index``).  ``tok[b]`` is the greedy token
    at slot b's last live row; ``vtok`` (B, spec_width) is the greedy
    argmax at each of the slot's leading rows — the verification targets
    of speculative decoding (row i scores the token that should follow the
    slot's i-th token).  With ``spec_width == 1`` no extra logits are
    computed and ``vtok`` is just ``tok[:, None]``.

    ``finite[b]`` is the on-device health scalar — one bool per slot,
    false when any logit the slot sampled from is non-finite — and the
    host quarantines such slots instead of emitting garbage.  ``poison``
    is an additive per-slot logit offset the chaos harness uses to inject
    NaN (all-zero in production): it is *data*, not a shape, so the
    no-retrace contract is untouched.

    Shared by :class:`ServingEngine` and the model drafter
    (``serve/speculative.ModelDraft``) — the drafter is mechanically a
    second, smaller serving engine riding the same step contract."""
    page_state = {
        "block_size": serve.block_size,
        "fused": bool(fused),
        "pages_per_tile": serve.pages_per_tile,
    }
    W = serve.mixed_slab_width

    def step_fn(params, pools, stream, tables, lens, q_lens, poison):
        if trace is not None:
            trace[trace_key] += 1
        B = lens.shape[0]
        tokens, slot, pos = stream
        flat, inv = slab_index(slot, pos, lens, q_lens, W)
        x, nc, _ = forward(
            params, {"tokens": tokens[:, None]}, cfg=cfg, plan=plan,
            cache={"layers": pools["layers"], "t": pos}, shard=shard,
            page_state={
                **page_state, "table": tables, "lens": lens, "q_lens": q_lens,
                "slot": slot, "flat": flat, "inv": inv,
            },
        )
        # the token at each slab row (T, so a zero row, where it is dead)
        rows = inv.reshape(B, W)
        fill = dict(axis=0, mode="fill", fill_value=0)
        # per-slot greedy token at the last live row (q_lens-1; row 0 for
        # decode slots, the final prompt token on a last prefill chunk)
        idx = jnp.maximum(q_lens - 1, 0)
        last = jnp.take_along_axis(rows, idx[:, None], axis=1)
        xl = jnp.take(x[:, 0], last[:, 0], **fill)
        logits = logits_fn(params, xl, cfg) + poison[:, None]
        tok = jnp.argmax(logits, axis=-1)
        # one extra scalar per slot: a NaN/Inf anywhere in the sampled
        # logits poisons the sum, so isfinite(sum) is the whole check
        finite = jnp.isfinite(jnp.sum(logits, axis=-1))
        if spec_width > 1:
            # verification targets: the target model's own greedy choice
            # after every leading row (drafted rows ride rows 1..gamma)
            xv = jnp.take(x[:, 0], rows[:, :spec_width], **fill)
            vlog = logits_fn(params, xv, cfg)
            vlog = vlog + poison[:, None, None]
            vtok = jnp.argmax(vlog, axis=-1)
            finite = finite & jnp.isfinite(jnp.sum(vlog, axis=(-2, -1)))
        else:
            vtok = tok[:, None]
        return tok, vtok, finite, {"layers": nc["layers"]}

    return jax.jit(step_fn, donate_argnums=(1,))


def make_rolled_step(
    cfg: ArchConfig,
    plan: ExecutionPlan,
    serve: ServePlan,
    *,
    fused: bool,
    shard: Callable = Identity,
    trace: Optional[dict] = None,
    trace_key: str = "rolled_step",
):
    """Build the rolled on-device decode loop: K decode iterations, ONE
    dispatch (the rolled-compilation idiom — ``lax.while_loop`` keeps the
    loop body compiled once, not unrolled).

    ``rolled(params, pools, tok (B,), tables (B, MB), lens (B,),
    steps_left (B,), k_steps ())`` runs up to ``k_steps`` decode iterations
    entirely on device: each iteration forwards every slot's current token
    as a width-1 slab, samples the greedy next token, repacks it as the
    next iteration's input, writes its KV at the slot's position and
    advances the per-slot length.  The host only sees the finished span.

    ``steps_left[b]`` is slot b's own iteration budget (0 = idle slot):
    a slot whose budget runs out mid-span goes *dead* — its row writes to
    the trash block, its sampled token freezes — while the others keep
    decoding, and the loop's ``cond`` exits early once every slot is done
    (the on-device analogue of per-slot EOS/max-len exit; the scheduler's
    event horizon guarantees nothing *else* needs the host mid-span).

    Returns ``(out (B, K), lens (B,), pools)``; ``out[b, :steps_left[b]]``
    are slot b's tokens in order (later columns hold -1).  ``k_steps`` and
    ``steps_left`` are data, not shapes — one compile serves every horizon
    the scheduler picks, so ``trace_counts["rolled_step"]`` stays at 1.
    The static ``K = serve.rolled_steps`` only sizes the output buffer.

    Fault tolerance inside the span: the loop carries a sticky per-slot
    *dead* flag set the first iteration the slot's logits go non-finite
    (``poison[b]`` lets the chaos harness force that at a chosen offset,
    -1 = never; it is data, not a shape).  A dead slot stops advancing —
    its length freezes at the last good position and its remaining output
    columns stay -1 — so the host sees exactly where to replay from while
    the healthy slots finish their spans.
    """
    page_state = {
        "block_size": serve.block_size,
        "fused": bool(fused),
        "pages_per_tile": serve.pages_per_tile,
    }
    K = int(serve.rolled_steps)

    def rolled_fn(params, pools, tok, tables, lens, steps_left, k_steps, poison):
        if trace is not None:
            trace[trace_key] += 1
        B = tok.shape[0]
        slots = jnp.arange(B, dtype=jnp.int32)

        def cond(state):
            i, dead = state[0], state[5]
            return jnp.logical_and(i < k_steps, jnp.any((steps_left > i) & ~dead))

        def body(state):
            i, tok, lens, layers, out, dead = state
            live = (steps_left > i) & ~dead
            kinds = live.astype(jnp.int32)
            # a packed stream of B tokens, one a slot, in a width-1 slab
            flat, inv = slab_index(slots, lens, lens, kinds, 1)
            x, nc, _ = forward(
                params, {"tokens": tok[:, None]}, cfg=cfg, plan=plan,
                cache={"layers": layers, "t": lens}, shard=shard,
                page_state={
                    **page_state, "table": tables, "lens": lens,
                    "q_lens": kinds, "slot": slots, "flat": flat, "inv": inv,
                },
            )
            logits = logits_fn(params, x, cfg)[:, -1]
            logits = logits + jnp.where(poison == i, jnp.float32(jnp.nan), 0.0)[
                :, None
            ]
            ok = jnp.isfinite(jnp.sum(logits, axis=-1))
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            good = live & ok
            return (
                i + 1,
                jnp.where(good, nxt, tok),
                lens + good.astype(jnp.int32),
                nc["layers"],
                out.at[:, i].set(jnp.where(good, nxt, -1)),
                dead | (live & ~ok),
            )

        _, _, lens, layers, out, _ = jax.lax.while_loop(
            cond,
            body,
            (
                jnp.int32(0), tok, lens, pools["layers"],
                jnp.full((B, K), -1, jnp.int32),
                jnp.zeros((B,), bool),
            ),
        )
        return out, lens, {"layers": layers}

    return jax.jit(rolled_fn, donate_argnums=(1,))


def traces_bounded(trace_counts: dict) -> bool:
    """The engine's no-retrace contract: at most TWO compiled device
    programs in normal operation — the unified mixed step (exactly once)
    and, when rolling is enabled and engaged, the rolled decode loop (at
    most once).  Request churn, draft depth, horizon K and the chaos
    harness's NaN-poison vector are data, never shapes.  The one sanctioned
    extra compile is the degradation ladder's bottom rung: the eager gather
    fallback (``fallback_step``), built lazily and at most once, and only
    after transient faults exhausted the fused rungs."""
    return (
        set(trace_counts) <= {"step", "rolled_step", "fallback_step"}
        and trace_counts.get("step") == 1
        and trace_counts.get("rolled_step", 0) <= 1
        and trace_counts.get("fallback_step", 0) <= 1
    )


def _by_tenant(finished: list) -> dict:
    groups: dict = {}
    for r in finished:
        groups.setdefault(r.tenant, []).append(r)
    return groups


def _percentiles(xs: list) -> Optional[dict]:
    """Latency summary of a sample list; None when there are no samples.

    Always carries ``n``: with one sample every percentile is that sample
    (numpy's interpolation degenerates), which is statistically meaningless
    without the count — callers (and humans reading BENCH json) need it to
    judge whether p99 is a tail or an artifact."""
    if not xs:
        return None
    arr = np.asarray(xs, np.float64)
    return {
        "n": int(arr.size),
        "mean": float(arr.mean()),
        "p50": float(np.percentile(arr, 50)),
        "p90": float(np.percentile(arr, 90)),
        "p99": float(np.percentile(arr, 99)),
    }


def _since(reqs: list, start: str, end: str) -> Optional[dict]:
    """Percentiles of ``end - start`` (two ``Request.t_*`` fields) over the
    requests that stamped both."""
    pairs = [(getattr(r, start), getattr(r, end)) for r in reqs]
    return _percentiles([b - a for a, b in pairs if a is not None and b is not None])


class ServingEngine:
    """Continuous-batching serving over the paged KV cache.

    Exactly ONE jitted device program with static shapes:

    * ``step(params, pools, stream (3, T), tables (B, MB), lens (B,),
      kinds (B,), poison (B,))`` — a packed stream of ``T =
      serve.step_tokens`` tokens, each with its slot and position;
      ``kinds[b]`` is slot b's live token count (0 idle or deferred, 1
      decode, n <= W prefill chunk), ``lens[b]`` the absolute position of
      its first token.  Each slot's KV rides its own block-table row, the
      fused paged-attention kernel masks per slot, and the returned greedy
      token is taken at the slot's last live row — a runner's next token,
      or the first output of a request whose final prompt chunk this was.

    The scheduler packs the stream per iteration: admit, grow, one mixed
    step.  ``trace_counts`` proves there is no per-request retracing — it
    stays bounded by {"step": 1, "rolled_step": 1} however the stream
    churns (the second program is the rolled decode loop, compiled at most
    once; absent when rolling is off), including with speculative decoding
    on (draft depth varies per slot per iteration, but only the *values*
    of ``kinds`` change, never a shape).

    When ``serve.rolled_steps > 1`` (and speculation is off) the engine
    also builds the rolled on-device decode loop: whenever the scheduler's
    event horizon says no host event falls due for K >= 2 iterations, one
    ``step()`` call dispatches K decode iterations as one device program
    (``make_rolled_step``) and advances the iteration clock by the span.
    Greedy outputs are byte-identical to the K=1 path by construction —
    the loop body is the same forward/argmax on the same paged state.

    ``draft`` (a ``serve/speculative`` DraftSource) + ``serve.spec_len`` > 0
    turn decode slots speculative: each running slot's drafted continuation
    rides its slab row as gamma+1 rows (mechanically a prefill chunk), the
    step scores every row, and the host keeps the longest draft prefix that
    matches the target's own greedy argmax — output tokens are identical to
    the non-speculative engine by construction, rollback is just the
    per-slot length vector.
    """

    def __init__(
        self,
        params: PyTree,
        cfg: ArchConfig,
        plan: ExecutionPlan,
        serve: ServePlan,
        *,
        shardings=None,
        fused: Optional[bool] = None,
        draft=None,
        injector: Optional[FaultInjector] = None,
        obs: Optional[Observability] = None,
        hw: Optional[HardwareSpec] = None,
    ):
        ok, reason = serve_feasible(cfg)
        if not ok:
            raise ValueError(f"{cfg.name} cannot serve continuously: {reason}")
        self.cfg, self.plan, self.serve = cfg, plan, serve
        # observability bundle: metrics always on (pure host arithmetic),
        # lifecycle tracing only when the caller enabled it on
        # the bundle — emission can never touch shapes or device work
        self.obs = obs if obs is not None else Observability()
        self.hw = hw if hw is not None else resolve_hardware(DEFAULT_HARDWARE.name)
        self.sched = Scheduler(serve, obs=self.obs)
        self.params = params
        # One program writes the zero pools at their final placement: built
        # eagerly, the per-layer pools, their stack and a placed copy would
        # all be live at once, and at full size they do not fit next to the
        # weights.
        pools_fn = lambda: init_paged_cache(cfg, plan, serve)  # noqa: E731
        self.pools = jax.jit(
            pools_fn,
            out_shardings=None
            if shardings is None
            else shardings.cache_shardings(jax.eval_shape(pools_fn)),
        )()
        shard = shardings.constrain if shardings is not None else Identity
        self._shard = shard
        self.injector = injector
        if injector is not None:
            injector.bind(self.obs)
        if fused is None:
            # GSPMD cannot partition the Pallas call over a multi-device
            # mesh yet (ROADMAP: shard_map decode); those engines fall
            # back to the gather path, everything else runs the kernel
            # (a single-device Shardings is just an identity placement).
            fused = serve.fused_attention and (
                shardings is None or shardings.mesh.size == 1
            )
        self.fused = bool(fused)
        # the kernel's table tile as ``kernels/paged_attention/ops`` sizes
        # it, and the attention window (every servable model's attention
        # layers share one), for the tile counters
        mb = serve.max_blocks_per_seq
        ppt = largest_divisor_of(mb, serve.pages_per_tile or mb)
        layer_kinds = {cfg.layer_kind(i) for i in range(cfg.n_layers)}
        window = (
            cfg.sliding_window if "swa" in layer_kinds
            else cfg.local_window if "local" in layer_kinds else 0
        )
        self._sweep = {"tile": ppt * serve.block_size, "n_tiles": mb // ppt, "window": window}
        self.draft = draft
        if draft is not None and hasattr(draft, "bind_obs"):
            draft.bind_obs(self.obs)
        self.spec_len = serve.spec_len if draft is not None else 0
        if self.spec_len >= serve.mixed_slab_width and serve.mixed_slab_width > 0:
            # plan clamps this already; belt-and-braces for hand-built plans
            self.spec_len = serve.mixed_slab_width - 1
        self.trace_counts = {"step": 0}
        self.iteration = 0
        self.stats = {
            "steps": 0, "prefill_tokens": 0, "generated_tokens": 0,
            "draft_rows": 0, "accepted_drafts": 0, "spec_slots": 0,
            "spec_generated": 0, "fork_copies": 0, "occupancy_sum": 0.0,
            "rolled_dispatches": 0, "rolled_steps": 0,
            "retries": 0, "transient_faults": 0, "rung_escalations": 0,
            "rung_recoveries": 0, "quarantines": 0, "poisoned": 0,
            "expired": 0, "shed": 0, "cancelled": 0, "injected_nans": 0,
            # seconds in each phase of step() (``self._phases``); they tile
            # the call, and launch_s + sync_s is the dispatches' wall time
            "admit_s": 0.0, "pack_s": 0.0, "launch_s": 0.0, "sync_s": 0.0,
            "harvest_s": 0.0,
            # query rows per KV head and layer the paged kernel computes on
            # a live tile, and those carrying a token; the (slot, tile)
            # sweeps it makes a layer, the keys those tiles fetch and the
            # keys the live rows attend (``_count_attn_rows``)
            "attn_rows": 0, "attn_live_rows": 0,
            "attn_tiles": 0, "attn_keys_fetched": 0, "attn_live_keys": 0,
            # rows the dense layers run (``step_tokens`` a mixed step, B a
            # rolled iteration), and how often the step's token budget left
            # a prefilling slot short, by how many rows
            "rows_computed": 0, "budget_bound_steps": 0,
            "prefill_rows_deferred": 0,
        }
        self._phases = Phases(self.obs.tracer, self.stats)
        # degradation ladder: 0 = rolled-K spans, 1 = K=1 mixed step,
        # 2 = eager gather fallback (built lazily).  Transient-fault
        # retries that exhaust retry_limit step DOWN; ladder_recovery
        # consecutive healthy dispatches step back UP.
        self.rung = 0
        self._healthy = 0
        self._gather = None
        self._last_fault: Optional[dict] = None
        self._no_poison = np.zeros((serve.decode_batch,), np.float32)
        # copy-on-write fork: one jitted block copy, reused for every fork
        # (block ids are data, not shapes — compiles once, retraces never;
        # deliberately NOT counted in ``trace_counts``, which is the mixed
        # step's no-retrace invariant)
        self._copy = jax.jit(paged_copy_block, donate_argnums=(0,))
        # verify-row width follows the *engine's* draft-gated depth, not the
        # plan's: a speculative plan served without a draft source must not
        # pay spec_len+1 rows of discarded vocab logits every step
        self._step = make_mixed_step(
            cfg, plan, serve, fused=self.fused, shard=shard,
            spec_width=self.spec_len + 1 if self.spec_len > 0 else 1,
            trace=self.trace_counts,
        )
        # rolled on-device decode loop: K iterations per dispatch, used
        # whenever the scheduler's event horizon allows K >= 2.  Gated off
        # under speculation — draft accept/rollback is a host event every
        # iteration, so the horizon would always be 1 anyway.
        self.rolled_cap = int(serve.rolled_steps) if self.spec_len == 0 else 1
        if self.rolled_cap > 1:
            self.trace_counts["rolled_step"] = 0
            self._rolled = make_rolled_step(
                cfg, plan, serve, fused=self.fused, shard=shard,
                trace=self.trace_counts,
            )
        else:
            self._rolled = None
        # engines without a rolled loop live on the "mixed" rung; recovery
        # never climbs above the floor
        self._rung_floor = 0 if self._rolled is not None else 1
        self.rung = self._rung_floor
        self.obs.m_rung.set(self.rung)

    # ------------------------------------------------------------------ API
    def submit(self, req: Request) -> None:
        """Queue a request, validating the construction fields up front —
        a malformed request must fail here with the field named, not steps
        later inside the scheduler as an opaque shape error."""
        if not req.prompt:
            raise ValueError(f"request {req.rid}: prompt must not be empty")
        if req.max_new_tokens <= 0:
            raise ValueError(
                f"request {req.rid}: max_new_tokens must be positive,"
                f" got {req.max_new_tokens}"
            )
        if len(req.prompt) >= self.serve.max_seq_len:
            raise ValueError(
                f"request {req.rid}: prompt length {len(req.prompt)}"
                f" >= max_seq_len {self.serve.max_seq_len}"
            )
        vocab = self.cfg.vocab_size
        for i, t in enumerate(req.prompt):
            if not 0 <= int(t) < vocab:
                raise ValueError(
                    f"request {req.rid}: prompt token id {int(t)} at"
                    f" position {i} outside vocab range [0, {vocab})"
                )
        if req.deadline_ms is None:
            req.deadline_ms = self.serve.deadline_ms
        self.sched.submit(req)

    def cancel(self, rid: str) -> bool:
        """Cancel a queued or in-flight request by id, releasing its
        blocks/radix refs; returns False when no live request matches."""
        for r in list(self.sched.waiting) + [
            s for s in self.sched.slots if s is not None
        ]:
            if r.rid == rid:
                self.sched.cancel(r, status="cancelled")
                self.stats["cancelled"] += 1
                return True
        return False

    def reset_stats(self) -> None:
        """Zero the throughput counters, finished-request latency samples and
        the iteration clock (e.g. after a jit-warmup stream) — request
        arrivals are absolute iterations, so the clock must restart or a
        post-warmup 'staggered' stream arrives as a burst.  Compiled step
        caches and pool contents are left alone."""
        for k in self.stats:
            self.stats[k] = 0.0 if isinstance(self.stats[k], float) else 0
        self.stats.pop("wall_s", None)
        self.sched.finished = []
        self.sched.shed = []
        self.iteration = 0

    def _propose_drafts(self) -> dict:
        """Ask the draft source for each running slot's continuation.

        Depth per slot degrades gracefully: never more than the plan's
        gamma, never past the slab width (gamma+1 rows must fit next to the
        slot's real token), and never drafting tokens the request has no
        budget left to emit — a slot with no headroom simply decodes
        plainly.  Returns {rid: [draft tokens]}."""
        cap = min(self.spec_len, self.serve.mixed_slab_width - 1)
        if self.draft is None or cap <= 0:
            return {}
        if self.sched._slo_pressure():
            # draft rows widen every runner's slab share; while an SLO'd
            # prefill is at risk that width belongs to prompt chunks
            return {}
        asks = []
        for req in self.sched.running():
            n = min(cap, req.max_new_tokens - len(req.out) - 1)
            if n > 0:
                asks.append((req.rid, req.prompt + req.out, n))
        if not asks:
            return {}
        props = self.draft.propose(asks)
        return {rid: list(d) for rid, d in props.items() if d}

    # ------------------------------------------------- degradation ladder
    def _gather_step(self):
        """Rung-2 fallback: the same mixed step compiled without the fused
        Pallas kernel (dense gather attention).  Built lazily — production
        never pays its compile unless the ladder actually reaches it — and
        traced under its own key so the no-retrace contract stays auditable
        (``fallback_step`` <= 1)."""
        if self._gather is None:
            self.trace_counts.setdefault("fallback_step", 0)
            self._gather = make_mixed_step(
                self.cfg, self.plan, self.serve, fused=False, shard=self._shard,
                spec_width=self.spec_len + 1 if self.spec_len > 0 else 1,
                trace=self.trace_counts, trace_key="fallback_step",
            )
        return self._gather

    def _escalate(self) -> bool:
        """Step one rung down the ladder; False when already at the bottom."""
        if self.rung >= len(LADDER) - 1:
            return False
        self.rung += 1
        self._healthy = 0
        self.stats["rung_escalations"] += 1
        self.obs.on_rung("down", self.rung, LADDER[self.rung])
        return True

    def _note_healthy(self) -> None:
        self._healthy += 1
        if self.rung > self._rung_floor and self._healthy >= self.serve.ladder_recovery:
            self.rung -= 1
            self._healthy = 0
            self.stats["rung_recoveries"] += 1
            self.obs.on_rung("up", self.rung, LADDER[self.rung])

    def _note_fault(self, kind: str, detail: str) -> None:
        self.stats["transient_faults"] += 1
        self._healthy = 0
        self._last_fault = {
            "kind": kind, "iteration": self.iteration, "detail": detail,
        }

    def _backoff(self, attempt: int) -> None:
        base = self.serve.retry_backoff_s
        if base > 0:
            time.sleep(min(base * 2 ** (attempt - 1), 0.25))

    def _retry_transients(self) -> bool:
        """Absorb transient dispatch faults for the upcoming device call
        with bounded, exponentially backed-off retries.  True = cleared to
        dispatch; False = this rung's retry budget is spent (the caller
        escalates).  The check runs *before* the jitted call, so a failed
        attempt never consumes the donated pool buffers.  (A production
        backend would map real device errors — e.g. XlaRuntimeError — to
        :class:`TransientDeviceError` at the same boundary.)"""
        attempts = 0
        while True:
            if self.injector is None:
                return True
            try:
                self.injector.check_dispatch(self.iteration)
                return True
            except TransientDeviceError as e:
                self._note_fault("transient", str(e))
                attempts += 1
                if attempts > self.serve.retry_limit:
                    return False
                self.stats["retries"] += 1
                self.obs.on_retry()
                self._backoff(attempts)

    def _poison_vec(self, kinds: np.ndarray) -> np.ndarray:
        """Per-slot additive logit poison for this iteration (chaos NaN
        injection), masked to occupied slots; all-zero without an injector."""
        if self.injector is None:
            return self._no_poison
        mask = self.injector.nan_mask(self.iteration, self.serve.decode_batch)
        mask = mask & (np.asarray(kinds) > 0)
        n = int(mask.sum())
        if n == 0:
            return self._no_poison
        self.injector.counts["nan"] += n
        self.stats["injected_nans"] += n
        # NaN injections are emitted here, not by the injector: only the
        # engine knows how many poisons actually landed on occupied slots
        self.obs.on_fault(
            "nan", seed=self.injector.seed, salt=SALTS["nan"],
            iteration=self.iteration, slots=n,
        )
        v = np.zeros((self.serve.decode_batch,), np.float32)
        v[mask] = np.nan
        return v

    def step(self) -> None:
        """One engine iteration: pressure/expiry -> admit -> shed -> fork
        copies -> draft -> grow -> one unified mixed step -> accept/rollback.

        When the rolled loop is enabled, the ladder sits at its top rung,
        and the scheduler's event horizon allows K >= 2 decode iterations
        before the next host-required event, one call dispatches the rolled
        step instead — K iterations, one device program — and the iteration
        clock advances by the span.  Fallback to the ordinary K=1 slab is
        transparent (same tokens, the differential harness asserts byte
        identity).

        Fork copies are applied immediately after admission, before anything
        can release blocks (growth/eviction run later in the iteration), so
        a copy's source block is still resident when the device reads it.

        The call is tiled into phases (``self._phases``; docs/OBSERVABILITY.md
        §Engine phases): ``engine.admit`` (pressure, expiry, admission,
        shedding, fork copies), ``engine.pack`` (the rolled plan, drafts,
        growth, the slab, its price, the retry ladder, the poison vector),
        ``engine.launch`` (the jitted call until it returns), ``engine.sync``
        (blocking on its outputs) and ``engine.harvest`` (accept/rollback,
        hooks, stats)."""
        with self._phases:
            self._iterate()

    def _iterate(self) -> None:
        """The body of ``step()``, marking each phase as it begins."""
        s, ph = self.sched, self._phases
        now = ph.to("engine.admit")
        if self.injector is not None:
            self.injector.pressure(self.iteration, s.alloc)
        self.stats["expired"] += s.expire_deadlines(now)
        s.admit(self.iteration)
        self.stats["shed"] += s.shed_starved(self.iteration)
        for src, dst in s.drain_copies():
            self.pools = self._copy(
                self.pools, jnp.int32(src), jnp.int32(dst)
            )
            self.stats["fork_copies"] += 1
        ph.to("engine.pack")
        if self._rolled is not None and self.rung == 0:
            k, steps = s.plan_rolled(self.iteration, self.rolled_cap)
            if k > 1 and self._rolled_dispatch(k, steps):
                return
            # retry exhaustion escalated mid-plan: fall through to the K=1
            # path this iteration (pre-reserved span blocks stay with their
            # slots; decode just proceeds one step at a time)
        drafts = self._propose_drafts()
        s._grow_for_decode({rid: len(d) for rid, d in drafts.items()})
        if s.busy():
            slab = s._slab_view(self.serve.mixed_slab_width, drafts)
            kinds = slab.kinds
            # slab composition, snapshotted pre-dispatch
            # (``_slab_done`` mutates slot states); a prefilling slot the
            # budget left out counts as idle this step
            composition = {
                "idle": int((kinds == 0).sum()),
                "decode": int((kinds == 1).sum()),
                "prefill": sum(1 for r in s.prefilling() if kinds[r.slot]),
                "spec": sum(1 for r in s.running() if r.rid in drafts),
            }
            rows = int(kinds.sum())
            phase = "prefill" if composition["prefill"] else "decode"
            while not self._retry_transients():
                if not self._escalate():
                    raise LadderExhausted(
                        "transient dispatch faults exhausted the retry ladder",
                        self.health(),
                    )
            step_fn = self._step if self.rung < 2 else self._gather_step()
            poison = self._poison_vec(kinds)
            trace_key = "step" if self.rung < 2 else "fallback_step"
            traces_before = self.trace_counts[trace_key]
            t0 = ph.to("engine.launch")
            if self.injector is not None:
                sp = self.injector.spike_s(self.iteration)
                if sp:
                    time.sleep(sp)
            sampled, vtok, finite, self.pools = step_fn(
                self.params, self.pools, slab.stream, slab.tables, slab.lens,
                kinds, jnp.asarray(poison),
            )
            ph.to("engine.sync")
            sampled = np.asarray(sampled)  # block for an honest step time
            vtok = np.asarray(vtok)
            finite = np.asarray(finite)
            t1 = ph.to("engine.harvest")
            dt_ms = (t1 - t0) * 1e3
            self._note_healthy()
            compiled_now = self.trace_counts[trace_key] != traces_before
            if not compiled_now:
                # feed SLO chunk sizing a compile-free step-time estimate
                s.step_ms = (
                    dt_ms if s.step_ms is None else 0.8 * s.step_ms + 0.2 * dt_ms
                )
            self.obs.on_dispatch(
                trace_key, phase, t0, t1, rows=rows,
                composition=composition, rung=LADDER[self.rung],
            )
            c = s._slab_done(
                sampled, kinds, vtok, drafts, finite=finite, span=(t0, t1)
            )
            self.obs.on_step_counts(c)
            self.obs.set_pool(
                available=s.alloc.available, in_use=s.alloc.in_use,
                active=len(s._active()), queued=len(s.waiting),
            )
            self.stats["steps"] += 1
            self.stats["prefill_tokens"] += c["prefill"]
            self.stats["generated_tokens"] += c["generated"]
            self.stats["draft_rows"] += c["draft_rows"]
            self.stats["accepted_drafts"] += c["accepted_drafts"]
            self.stats["spec_slots"] += c["spec_slots"]
            self.stats["spec_generated"] += c["spec_generated"]
            self.stats["quarantines"] += c["quarantined"]
            self.stats["poisoned"] += c["poisoned"]
            self.stats["occupancy_sum"] += (
                int((kinds > 0).sum()) / self.serve.decode_batch
            )
            self.stats["rows_computed"] += len(slab.tokens)
            self.stats["budget_bound_steps"] += int(slab.deferred > 0)
            self.stats["prefill_rows_deferred"] += slab.deferred
            if self.fused and trace_key == "step":
                self._count_attn_rows(slab.lens, kinds, self.serve.mixed_slab_width)
        self.iteration += 1

    def _rolled_dispatch(self, k: int, steps: np.ndarray) -> bool:
        """Run one rolled span: up to ``k`` decode iterations in ONE device
        dispatch (per-slot budgets ``steps``, blocks already pre-reserved by
        ``plan_rolled``).  Host bookkeeping happens once for the whole span;
        the iteration clock and the per-step stats advance by the span
        length so rolled and K=1 runs stay comparable.

        Returns False when transient faults spent this rung's retry budget
        — the ladder escalated to the K=1 mixed rung and the caller falls
        through to it for this iteration."""
        s, ph = self.sched, self._phases
        if not self._retry_transients():
            self._escalate()
            return False
        tok0 = np.zeros((self.serve.decode_batch,), np.int32)
        for b, req in enumerate(s.slots):
            if req is not None and steps[b] > 0:
                tok0[b] = req.out[-1]
        poison = np.full((self.serve.decode_batch,), -1, np.int32)
        if self.injector is not None:
            poison = self.injector.nan_in_span(
                self.iteration, k, self.serve.decode_batch
            )
            poison[np.asarray(steps) <= 0] = -1
            n = int((poison >= 0).sum())
            self.injector.counts["nan"] += n
            self.stats["injected_nans"] += n
            if n:
                self.obs.on_fault(
                    "nan", seed=self.injector.seed, salt=SALTS["nan"],
                    iteration=self.iteration, slots=n, span_k=int(k),
                )
        traces_before = self.trace_counts["rolled_step"]
        lens0 = np.array(s.lens)
        t0 = ph.to("engine.launch")
        if self.injector is not None:
            sp = self.injector.spike_s(self.iteration)
            if sp:
                time.sleep(sp)
        out, _, self.pools = self._rolled(
            self.params, self.pools, jnp.asarray(tok0),
            jnp.asarray(s.table), jnp.asarray(s.lens),
            jnp.asarray(steps, np.int32), jnp.int32(k),
            jnp.asarray(poison),
        )
        ph.to("engine.sync")
        out = np.asarray(out)  # block for an honest span time
        t1 = ph.to("engine.harvest")
        dt_ms = (t1 - t0) * 1e3
        self._note_healthy()
        adv = int(steps.max())  # device iterations actually executed
        compiled_now = self.trace_counts["rolled_step"] != traces_before
        if not compiled_now and adv > 0:
            # per-iteration estimate feeds the same SLO chunk-sizing EMA
            per = dt_ms / adv
            s.step_ms = per if s.step_ms is None else 0.8 * s.step_ms + 0.2 * per
        live = int((np.asarray(steps) > 0).sum())
        self.obs.on_dispatch(
            "rolled_step", "decode", t0, t1, rows=live,
            composition={
                "idle": self.serve.decode_batch - live, "decode": live,
            },
            rung=LADDER[self.rung], k=adv,
        )
        c = s._rolled_done(out, steps, span=(t0, t1))
        self.obs.on_step_counts(c)
        self.obs.set_pool(
            available=s.alloc.available, in_use=s.alloc.in_use,
            active=len(s._active()), queued=len(s.waiting),
        )
        self.stats["steps"] += adv
        self.stats["rolled_dispatches"] += 1
        self.stats["rolled_steps"] += adv
        self.stats["generated_tokens"] += c["generated"]
        self.stats["quarantines"] += c["quarantined"]
        self.stats["poisoned"] += c["poisoned"]
        # same unit as the K=1 path: live-slot fraction summed per device
        # iteration (slot b is live for its first steps[b] iterations)
        self.stats["occupancy_sum"] += float(steps.sum()) / self.serve.decode_batch
        self.stats["rows_computed"] += self.serve.decode_batch * adv
        if self.fused:
            # iteration j of the span: slot b decodes at lens0[b] + j while
            # j < steps[b]
            j = np.arange(adv)[:, None]
            self._count_attn_rows(lens0[None] + j, np.asarray(steps)[None] > j, 1)
        self.iteration += adv
        return True

    def _count_attn_rows(self, lens, q_lens, width: int) -> None:
        """Add one layer's kernel work over dispatched slots with ``lens``
        cached positions and ``q_lens`` live queries in a slab of
        ``width`` (arrays of any shape, one entry a slot and iteration):
        ``attn_rows`` (rows per KV head the kernel computes on a live tile,
        by its own rule) and ``attn_live_rows`` (G per live query);
        ``attn_tiles`` (live table tiles swept, by the kernel's own
        :func:`live_tiles`), ``attn_keys_fetched`` (their keys) and
        ``attn_live_keys`` (keys the live rows attend: the slot's context
        to its last query, from the first query's window)."""
        G = self.cfg.n_heads // self.cfg.n_kv_heads
        lens = np.asarray(lens, np.int64)
        q_lens = np.asarray(q_lens, np.int64)
        _, tiles = live_tiles(lens, q_lens, **self._sweep, xp=np)
        w = self._sweep["window"]
        first_key = np.maximum(lens - (w - 1), 0) if w > 0 else 0
        self.stats["attn_rows"] += int(rows_computed(G, width, q_lens).sum())
        self.stats["attn_live_rows"] += int((G * q_lens).sum())
        self.stats["attn_tiles"] += int(tiles.sum())
        self.stats["attn_keys_fetched"] += int(tiles.sum()) * self._sweep["tile"]
        self.stats["attn_live_keys"] += int(np.where(q_lens > 0, lens + q_lens - first_key, 0).sum())

    def run(self, requests=(), max_iterations: int = 100_000) -> dict:
        """Drive the stream to completion; returns {rid: generated tokens}
        for requests that *finished* (shed/expired/cancelled requests are
        reported through ``summary()``, not here).

        A stall detector watches for iterations that make no progress at
        all — no token emitted, no prompt row consumed, no admission, no
        completion or shedding — while work is actually pending (a future
        arrival idling the engine is not a stall).  ``stall_limit``
        consecutive dead iterations raise :class:`StallError` carrying
        ``health()`` instead of silently burning ``max_iterations``."""
        for r in requests:
            self.submit(r)
        t0 = time.perf_counter()
        sig = None
        stalled = 0
        while not self.sched.idle and self.iteration < max_iterations:
            self.step()
            s = self.sched
            cur = (
                self.stats["generated_tokens"],
                self.stats["prefill_tokens"],
                s.n_admissions,
                len(s.finished) + len(s.shed),
            )
            idle_by_design = all(x is None for x in s.slots) and all(
                r.arrival > self.iteration for r in s.waiting
            )
            if cur != sig or idle_by_design:
                sig, stalled = cur, 0
            else:
                stalled += 1
                if stalled >= self.serve.stall_limit:
                    raise StallError(
                        f"engine made no progress for {stalled} consecutive"
                        f" iterations (iteration {self.iteration})",
                        self.health(),
                    )
        self.stats["wall_s"] = time.perf_counter() - t0
        if not self.sched.idle:
            raise RuntimeError(f"stream not drained after {max_iterations} iters")
        return {r.rid: list(r.out) for r in self.sched.finished}

    # -------------------------------------------------- health + snapshot
    def health(self) -> dict:
        """Instantaneous engine health — cheap enough to poll every step,
        attached to StallError/LadderExhausted diagnostics."""
        s = self.sched
        arrived = sum(1 for r in s.waiting if r.arrival <= self.iteration)
        return {
            "iteration": self.iteration,
            "rung": self.rung,
            "rung_name": LADDER[self.rung],
            "healthy_streak": self._healthy,
            "retries": self.stats["retries"],
            "transient_faults": self.stats["transient_faults"],
            "quarantines": self.stats["quarantines"],
            "shed": self.stats["shed"],
            "expired": self.stats["expired"],
            "cancelled": self.stats["cancelled"],
            "poisoned": self.stats["poisoned"],
            "pool": {
                "n_blocks": self.serve.n_blocks,
                "available": s.alloc.available,
                "in_use": s.alloc.in_use,
            },
            "slots": {
                "running": len(s.running()),
                "prefilling": len(s.prefilling()),
                "free": s.slots.count(None),
            },
            "queue": {"arrived": arrived, "future": len(s.waiting) - arrived},
            "step_ms": s.step_ms,
            "last_fault": self._last_fault,
        }

    @staticmethod
    def _freeze(req: Request) -> dict:
        return {
            "rid": req.rid,
            "prompt": [int(t) for t in req.prompt],
            "max_new_tokens": int(req.max_new_tokens),
            "arrival": int(req.arrival),
            "tenant": req.tenant,
            "priority": int(req.priority),
            "slo_ttft_ms": req.slo_ttft_ms,
            "tag": req.tag,
            "deadline_ms": req.deadline_ms,
            "out": [int(t) for t in req.out],
            "status": req.status,
            "quarantines": int(req.quarantines),
        }

    @staticmethod
    def _thaw(rec: dict) -> Request:
        req = Request(
            rid=rec["rid"],
            prompt=list(rec["prompt"]),
            max_new_tokens=rec["max_new_tokens"],
            arrival=rec["arrival"],
            tenant=rec["tenant"],
            priority=rec["priority"],
            slo_ttft_ms=rec["slo_ttft_ms"],
            tag=rec["tag"],
            deadline_ms=rec["deadline_ms"],
        )
        req.out = list(rec["out"])
        req.status = rec["status"]
        req.quarantines = rec["quarantines"]
        return req

    def snapshot(self) -> dict:
        """JSON-serializable logical engine state: scheduler queues, request
        progress and the accounting counters — deliberately NO KV tensors
        and no allocator layout.  KV pages are a pure function of each
        request's token prefix (the PR 6 invariant), so ``restore`` on a
        fresh engine re-prefills every in-flight request's prompt + emitted
        tokens and the continuation is byte-identical; serialized state
        stays kilobytes however large the pools are.  Call between steps
        (the engine never yields mid-step)."""
        s = self.sched
        return {
            "version": 1,
            "arch": self.cfg.name,
            "iteration": self.iteration,
            "serve_plan": self.serve.to_record(),
            "active": [
                self._freeze(r) for r in s.slots if r is not None
            ],
            "waiting": [self._freeze(r) for r in s.waiting],
            "finished": [self._freeze(r) for r in s.finished],
            "shed": [self._freeze(r) for r in s.shed],
            "stats": {
                k: v for k, v in self.stats.items() if isinstance(v, (int, float))
            },
            "sched_counters": {
                "n_admissions": s.n_admissions,
                "n_evictions": s.n_evictions,
                "n_forks": s.n_forks,
                "n_prefix_hits": s.n_prefix_hits,
                "prefix_tokens_saved": s.prefix_tokens_saved,
            },
        }

    def restore(self, snap: dict) -> None:
        """Resume a snapshot on this (fresh, idle) engine.

        Finished/shed requests come back purely as records (accounting
        continuity); in-flight and queued requests re-enter the waiting
        queue with their emitted tokens preserved — admission prefills
        ``prompt + out[:-1]`` and the slot continues decoding from its
        last token, byte-identically (KV pages are a pure function of the
        prefix).  Deadline clocks restart at restore time: wall-clock
        timestamps from the crashed process are meaningless here."""
        if snap.get("arch") != self.cfg.name:
            raise ValueError(
                f"snapshot arch {snap.get('arch')!r} != engine {self.cfg.name!r}"
            )
        s = self.sched
        if not s.idle or s.finished or s.shed:
            raise RuntimeError("restore() needs a fresh idle engine")
        self.iteration = int(snap["iteration"])
        for rec in snap["finished"]:
            req = self._thaw(rec)
            req.state = DONE
            s.finished.append(req)
        for rec in snap["shed"]:
            req = self._thaw(rec)
            req.state = DONE
            s.shed.append(req)
        for rec in snap["active"] + snap["waiting"]:
            req = self._thaw(rec)
            req.state = WAITING
            self.submit(req)
        for k, v in snap.get("stats", {}).items():
            if k in self.stats:
                self.stats[k] = v
        sc = snap.get("sched_counters", {})
        s.n_admissions = sc.get("n_admissions", 0)
        s.n_evictions = sc.get("n_evictions", 0)
        s.n_forks = sc.get("n_forks", 0)
        s.n_prefix_hits = sc.get("n_prefix_hits", 0)
        s.prefix_tokens_saved = sc.get("prefix_tokens_saved", 0)

    def summary(self) -> dict:
        """Engine accounting.  ``tok_per_s`` counts *emitted output tokens*
        only — not slab rows: prompt rows are reported separately as
        ``prefill_tokens`` and rejected draft rows are never counted, so
        throughput cannot be inflated by prefill traffic or by speculation
        that verifies nothing.

        Safe at any sample count: a cold engine (0 steps, 0 finished)
        reports None for every rate/percentile instead of dividing by zero,
        a step-driven engine (no ``run()``, so no ``wall_s``) falls back to
        accumulated device time for ``tok_per_s``, and percentile dicts
        carry ``n`` so a 1-sample p99 is recognizable as such."""
        d = max(self.stats["steps"], 1)
        fin = self.sched.finished
        shed = self.sched.shed
        spec_on = self.draft is not None and self.spec_len > 0
        device_s = self.stats["launch_s"] + self.stats["sync_s"]
        wall = self.stats.get("wall_s") or device_s or None

        def _dispositions(rs: list) -> dict:
            out = {"shed": 0, "expired": 0, "cancelled": 0, "poisoned": 0}
            for r in rs:
                if r.status in out:
                    out[r.status] += 1
            return out

        def _latencies(rs: list) -> dict:
            # as the client sees them, from submission (queue wait
            # included); prefill_s is admission -> first token
            return {
                "latency_s": _since(rs, "t_submit", "t_done"),
                "ttft_s": _since(rs, "t_submit", "t_first"),
                "prefill_s": _since(rs, "t_admit", "t_first"),
            }

        tenants = {}
        for t, rs in sorted(_by_tenant(fin + shed).items()):
            t_fin = [r for r in rs if r.status == "ok"]
            tenants[t] = {
                "finished": len(t_fin),
                **_latencies(t_fin),
                **_dispositions(rs),
            }
        return {
            "iterations": self.iteration,
            "steps": self.stats["steps"],
            "prefill_tokens": self.stats["prefill_tokens"],
            "generated_tokens": self.stats["generated_tokens"],
            "mean_occupancy": self.stats["occupancy_sum"] / d,
            "evictions": self.sched.n_evictions,
            "traces": dict(self.trace_counts),
            "fused_attention": self.fused,
            "wall_s": self.stats.get("wall_s"),
            "device_s": device_s,
            "step_ms": self.sched.step_ms,
            "tok_per_s": (
                self.stats["generated_tokens"] / wall if wall else None
            ),
            "rolled": {
                "enabled": self._rolled is not None,
                "cap": self.rolled_cap,
                "dispatches": self.stats["rolled_dispatches"],
                "rolled_steps": self.stats["rolled_steps"],
                "mean_span": (
                    self.stats["rolled_steps"] / self.stats["rolled_dispatches"]
                    if self.stats["rolled_dispatches"]
                    else None
                ),
            },
            **_latencies(fin),
            "tenants": tenants,
            "requests": {
                "finished": len(fin),
                **_dispositions(shed),
            },
            "faults": {
                "rung": self.rung,
                "rung_name": LADDER[self.rung],
                "retries": self.stats["retries"],
                "transient_faults": self.stats["transient_faults"],
                "rung_escalations": self.stats["rung_escalations"],
                "rung_recoveries": self.stats["rung_recoveries"],
                "quarantines": self.stats["quarantines"],
                "injected_nans": self.stats["injected_nans"],
                "shed": self.stats["shed"],
                "expired": self.stats["expired"],
                "cancelled": self.stats["cancelled"],
                "poisoned": self.stats["poisoned"],
                "injector": (
                    self.injector.summary() if self.injector is not None else None
                ),
            },
            "prefix": {
                "enabled": self.sched.index is not None,
                "admissions": self.sched.n_admissions,
                "hits": self.sched.n_prefix_hits,
                "hit_rate": (
                    self.sched.n_prefix_hits / self.sched.n_admissions
                    if self.sched.n_admissions
                    else None
                ),
                "tokens_saved": self.sched.prefix_tokens_saved,
                "forks": self.sched.n_forks,
                "fork_copies": self.stats["fork_copies"],
                "peak_blocks": self.sched.alloc.peak_in_use,
                "double_frees": self.sched.alloc.double_frees,
            },
            "spec": {
                "enabled": spec_on,
                "spec_len": self.spec_len,
                "draft": self.serve.draft,
                "draft_rows": self.stats["draft_rows"],
                "accepted_drafts": self.stats["accepted_drafts"],
                "acceptance_rate": (
                    self.stats["accepted_drafts"] / self.stats["draft_rows"]
                    if self.stats["draft_rows"]
                    else None
                ),
                # mean output tokens per speculating slot-step (> 1 means
                # speculation is beating plain decode on those steps)
                "tokens_per_spec_step": (
                    self.stats["spec_generated"] / self.stats["spec_slots"]
                    if self.stats["spec_slots"]
                    else None
                ),
                "draft_traces": (
                    dict(self.draft.trace_counts)
                    if spec_on and hasattr(self.draft, "trace_counts")
                    else None
                ),
            },
            "serve_plan": self.serve.to_record(),
        }
