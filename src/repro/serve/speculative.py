"""Speculative decoding: draft sources for the continuous-batching engine.

CAT's serving roofline proves decode is bandwidth-bound — one weight stream
per step with the MXU mostly idle.  Speculative decoding converts that idle
compute into tokens: a drafter proposes gamma cheap continuation tokens per
running slot, the engine packs them (plus the slot's real token) into the
step's packed token stream as a gamma+1-row verification chunk, and the
ONE jitted step scores every row at once.  The host keeps the longest draft
prefix matching the target's own greedy argmax, so the emitted tokens are
*exactly* what plain decode would have produced — any draft source only
changes speed, never tokens.  Rollback past rejected rows is the per-slot
length vector alone: the block table is untouched, the stale KV the dead
rows wrote is masked by the kernel and overwritten when the slot advances.

Two draft sources:

* :class:`NGramDraft` — prompt-lookup self-drafting: match the sequence's
  trailing n-gram against its own history and propose the tokens that
  followed last time.  No second model, no device work, cheap enough for
  the CPU-interpret CI matrix; shines on repetitive continuations.
* :class:`ModelDraft` — a small model (any ``configs/`` entry, e.g.
  smollm-135m drafting for qwen3-1.7b) with its *own* paged KV cache and
  its own single jitted mixed step (the same step contract as the target
  engine, one trace total).  Slot state is keyed by request id and
  self-heals: each proposal round diffs the target's actual sequence
  against what the drafter has cached and rolls its length vector back to
  the common prefix, so target-side eviction, slot reuse, and rejected
  drafts need no explicit invalidation protocol.

The draft *depth* is a plan decision (``ServePlan.spec_len``, derived in
``core/plan.derive_serve_plan`` from the compute-vs-bandwidth slack), not a
drafter property — the same joint hardware/model contract that sizes the
decode batch sizes gamma.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.hardware import DEFAULT_HARDWARE, HardwareSpec
from repro.core.plan import (
    SERVE_WEIGHT_DTYPE,
    ExecutionPlan,
    ServePlan,
    derive_plan,
    derive_serve_plan,
)
from repro.models.cache import init_paged_cache
from repro.serve.engine import make_mixed_step
from repro.serve.scheduler import BlockAllocator, pack

Ask = tuple  # (rid, full token sequence so far, max drafts wanted)


def prompt_lookup(
    seq: Sequence[int], n: int, max_ngram: int = 3, min_ngram: int = 1
) -> list[int]:
    """Propose up to ``n`` tokens by copying what followed an earlier
    occurrence of the sequence's trailing n-gram.

    Longest n-gram first; within one n-gram length the *most recent*
    occurrence whose continuation has all ``n`` tokens wins (a match at the
    sequence tail can only contribute a truncated draft — common on
    repeated-token runs — so it is kept only as the fallback when no
    occurrence anywhere has a full window).  Returns [] when no n-gram down
    to ``min_ngram`` recurs."""
    L = len(seq)
    fallback: list[int] = []
    for m in range(min(max_ngram, L - 1), min_ngram - 1, -1):
        pat = tuple(seq[L - m :])
        # scan right-to-left, excluding the suffix occurrence itself
        for i in range(L - m - 1, -1, -1):
            if tuple(seq[i : i + m]) == pat:
                cont = list(seq[i + m : i + m + n])
                if len(cont) == n:
                    return cont
                if not fallback:
                    fallback = cont
    return fallback


class NGramDraft:
    """Prompt-lookup self-drafting (no second model, host-side only)."""

    name = "ngram"

    def __init__(self, max_ngram: int = 3, min_ngram: int = 1):
        self.max_ngram = max_ngram
        self.min_ngram = min_ngram
        self.trace_counts: dict = {}  # no device program at all
        self.obs = None

    def bind_obs(self, obs) -> None:
        """Emit per-round draft counters into the engine's metrics registry
        (the engine binds its bundle at construction)."""
        self.obs = obs

    def propose(self, asks: list[Ask]) -> dict:
        out = {
            rid: prompt_lookup(seq, n, self.max_ngram, self.min_ngram)
            for rid, seq, n in asks
        }
        if self.obs is not None:
            self.obs.on_draft_round(
                self.name, len(asks), sum(len(d) for d in out.values())
            )
        return out


class ModelDraft:
    """Model drafting: a small config runs greedy continuation on its own
    paged cache through one jitted mixed step (the target engine's step
    contract in miniature).

    Proposal rounds are fully batched: every asking slot contributes rows
    to one packed draft stream per call (the drafter plan's own
    ``step_tokens``) — catch-up chunks (tokens the target emitted that the
    drafter has not cached yet, up to Wd a slot) and autoregressive draft
    rows ride the same step, so a round costs about
    ``ceil(max_catchup / Wd) + gamma - 1`` device calls, more where the
    catch-up outgrows the budget.
    """

    def __init__(
        self,
        params,
        cfg,
        plan: ExecutionPlan,
        serve: ServePlan,
        *,
        target_vocab: Optional[int] = None,
    ):
        self.cfg, self.plan, self.serve = cfg, plan, serve
        self.params = params
        self.name = cfg.name
        self.target_vocab = target_vocab
        self.pools = init_paged_cache(cfg, plan, serve)
        self.alloc = BlockAllocator(serve.n_blocks)
        B = serve.decode_batch
        self.table = np.zeros((B, serve.max_blocks_per_seq), np.int32)
        self.blocks: list[list[int]] = [[] for _ in range(B)]
        self.toks: list[list[int]] = [[] for _ in range(B)]  # cached tokens
        self.rids: list[Optional[str]] = [None] * B
        self.trace_counts = {"draft_step": 0}
        self.obs = None
        self._step = make_mixed_step(
            cfg, plan, serve, fused=serve.fused_attention,
            spec_width=1, trace=self.trace_counts, trace_key="draft_step",
        )
        # the drafter never injects chaos into its own step; drafts are
        # proposals, so a genuinely non-finite drafter just drafts garbage
        # the target's verification rejects
        self._no_poison = jnp.zeros((B,), jnp.float32)

    # ----------------------------------------------------------- slot state
    def _slot_for(self, rid: str, active: set) -> Optional[int]:
        """Slot of ``rid``, assigning (or stealing an inactive slot) on
        first sight.  At most ``decode_batch`` rids can ask per round (they
        occupy target slots), so a steal always finds a victim."""
        if rid in self.rids:
            return self.rids.index(rid)
        for b, r in enumerate(self.rids):
            if r is None:
                self.rids[b] = rid
                return b
        for b, r in enumerate(self.rids):
            if r not in active:
                self._release(b)
                self.rids[b] = rid
                return b
        return None

    def _release(self, b: int) -> None:
        if self.blocks[b]:
            self.alloc.free(self.blocks[b])
        self.blocks[b] = []
        self.table[b] = 0
        self.toks[b] = []
        self.rids[b] = None

    def _ensure_blocks(self, b: int, n_tokens: int) -> bool:
        bs = self.serve.block_size
        need = -(-n_tokens // bs) - len(self.blocks[b])
        if need <= 0:
            return True
        got = self.alloc.alloc(need)
        if got is None:
            return False  # pool dry: stop drafting this slot, never evict
        start = len(self.blocks[b])
        self.blocks[b].extend(got)
        self.table[b, start : len(self.blocks[b])] = got
        return True

    # -------------------------------------------------------------- drafting
    def propose(self, asks: list[Ask]) -> dict:
        """{rid: [<= n draft tokens]} for each (rid, seq, n) ask.

        Self-healing sync: the drafter's cache is valid only up to the
        longest common prefix of what it cached and the sequence the target
        actually kept — rejected drafts, evictions and slot churn all
        surface as a shorter prefix and cost nothing but re-feeding."""
        if not asks:
            return {}
        n_dispatches = 0
        active = {rid for rid, _, _ in asks}
        W, T = self.serve.mixed_slab_width, self.serve.step_tokens
        state = {}  # slot -> [pending rows to feed, drafts, want]
        for rid, seq, n in asks:
            b = self._slot_for(rid, active)
            if b is None:
                continue
            cached, p = self.toks[b], 0
            while p < min(len(cached), len(seq)) and cached[p] == seq[p]:
                p += 1
            # keep >= 1 token pending: after an eviction-recompute the cache
            # can cover ALL of seq (greedy is deterministic), but drafting
            # needs the argmax after seq's last token, so re-feed it
            p = min(p, len(seq) - 1)
            self.toks[b] = cached[:p]  # rollback = length only, blocks stay
            state[b] = [list(seq[p:]), [], int(n)]
        while True:
            feeding = {}  # slot -> rows packed this call
            budget = T  # a slot the budget leaves out is fed next call
            for b, (pending, drafts, want) in state.items():
                if len(drafts) >= want or budget == 0:
                    continue
                rows = pending[: min(W, budget)] if pending else [drafts[-1]]
                if not self._ensure_blocks(b, len(self.toks[b]) + len(rows)):
                    state[b][2] = len(drafts)  # pool dry: freeze this slot
                    continue
                feeding[b] = rows
                budget -= len(rows)
            if not feeding:
                break
            slab = pack(
                {b: (len(self.toks[b]), rows) for b, rows in feeding.items()},
                self.table, T,
            )
            tok, _, _, self.pools = self._step(
                self.params, self.pools, slab.stream, slab.tables, slab.lens,
                slab.kinds, self._no_poison,
            )
            n_dispatches += 1
            tok = np.asarray(tok)
            for b, rows in feeding.items():
                pending, drafts, want = state[b]
                self.toks[b].extend(rows)
                if pending:
                    del pending[: len(rows)]
                    if pending:
                        continue  # mid-catch-up argmax: discard
                t = int(tok[b])
                if self.target_vocab is not None and t >= self.target_vocab:
                    state[b][2] = len(drafts)  # unverifiable id: stop early
                    continue
                drafts.append(t)
        out = {
            self.rids[b]: drafts
            for b, (_, drafts, _) in state.items()
            if self.rids[b] is not None
        }
        if self.obs is not None:
            self.obs.on_draft_round(
                self.name, len(asks),
                sum(len(d) for d in out.values()),
                device_steps=n_dispatches,
            )
        return out

    def bind_obs(self, obs) -> None:
        """Emit per-round draft counters (asks, drafted tokens, device
        dispatches) into the engine's metrics registry."""
        self.obs = obs

    def summary(self) -> dict:
        return {
            "draft_model": self.name,
            "traces": dict(self.trace_counts),
            "serve_plan": self.serve.to_record(),
        }


def make_draft_source(
    name: Optional[str],
    target_cfg,
    target_serve: ServePlan,
    *,
    hw: HardwareSpec = DEFAULT_HARDWARE,
    params=None,
    seed: int = 0,
    reduced: bool = False,
):
    """Build the DraftSource named by a plan/CLI ``draft`` string.

    ``"none"``/None -> None, ``"ngram"`` -> :class:`NGramDraft`, anything
    else is a config name -> :class:`ModelDraft` with freshly initialized
    params (or ``params`` when the caller already has trained weights —
    passing the *target's* params turns it into a self-drafting oracle,
    useful as the acceptance upper bound)."""
    if name in (None, "", "none"):
        return None
    if name == "ngram":
        return NGramDraft()
    from repro.configs import get_config
    from repro.models.params import init_params

    cfg = get_config(name)
    if reduced:
        cfg = cfg.reduced()
    mesh = {"data": 1, "model": 1}
    plan = derive_plan(
        cfg, mesh, hw,
        batch=target_serve.decode_batch,
        seq_len=target_serve.prefill_chunk,
        training=False,
    )
    serve_d = derive_serve_plan(
        cfg, mesh, hw,
        max_seq_len=target_serve.max_seq_len,
        decode_batch=target_serve.decode_batch,
        block_size=target_serve.block_size,
        prefill_chunk=target_serve.prefill_chunk,
        mixed_slab_width=target_serve.mixed_slab_width,
        # same page precision as the target: a self-drafting oracle must
        # score the prefix through the same cache numerics or a near-tie
        # argmax can flip and quietly break the acceptance-1.0 bound
        kv_dtype=target_serve.kv_dtype,
    )
    if params is None:
        params = init_params(
            jax.random.PRNGKey(seed), cfg, plan, dtype=SERVE_WEIGHT_DTYPE
        )
    return ModelDraft(
        params, cfg, plan, serve_d, target_vocab=target_cfg.vocab_size
    )
