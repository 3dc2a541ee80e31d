"""EDPU — the Encoder/Decoder Processing Unit (paper §III.B) in JAX.

One ``edpu_layer`` call = one Transformer layer = MHA Stage then FFN Stage,
serially, sharing the same chips (the paper's two-stage resource-sharing
design).  Layers are stacked as scanned pattern-groups so heterogeneous
patterns (e.g. RecurrentGemma's rglru/rglru/local) stay scannable.

Everything is a pure function of (params, batch) with the ExecutionPlan as
static configuration — the plan is where the CAT customization (fused QKV,
chunk sizes, remat, MoE dispatch) enters.
"""
from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax

from jax import shard_map
from jax.sharding import PartitionSpec

from repro.configs.base import ArchConfig
from repro.core.plan import ExecutionPlan
from repro.models import cache as C
from repro.models import layers as L
from repro.models import moe as M
from repro.models import rglru as G
from repro.models import rwkv6 as R

PyTree = Any
Identity = lambda x, name=None: x


# ---------------------------------------------------------------------------
# Attention stage (the ATB + LBs)
# ---------------------------------------------------------------------------
def _project_qkv(ap: dict, h: jax.Array, cfg: ArchConfig, plan: ExecutionPlan):
    B, S, _ = h.shape
    H, KV, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    if plan.fuse_qkv and "wqkv" in ap:
        qkv = h @ ap["wqkv"]  # C5: one large MM instead of 3 narrow ones
        q, k, v = jnp.split(qkv, [H * Dh, (H + KV) * Dh], axis=-1)
    else:
        q, k, v = h @ ap["wq"], h @ ap["wk"], h @ ap["wv"]
    q = q.reshape(B, S, H, Dh)
    k = k.reshape(B, S, KV, Dh)
    v = v.reshape(B, S, KV, Dh)
    if cfg.qk_norm:
        q = L.rmsnorm(q, ap["q_norm"])
        k = L.rmsnorm(k, ap["k_norm"])
    return q, k, v


def attention_stage(
    ap: dict,
    h: jax.Array,
    *,
    cfg: ArchConfig,
    plan: ExecutionPlan,
    kind: str,
    positions: jax.Array,
    cache: Optional[dict],
    prefix_len: int,
    shard: Callable = Identity,
    page_state: Optional[dict] = None,
):
    B, S, _ = h.shape
    H, Dh = cfg.n_heads, cfg.d_head
    window = (
        cfg.sliding_window
        if kind == "swa"
        else cfg.local_window if kind == "local" else 0
    )
    q, k, v = _project_qkv(ap, h, cfg, plan)
    if cfg.pos_embedding == "rope":
        q = L.rope(q, positions, cfg.rope_theta)
        k = L.rope(k, positions, cfg.rope_theta)
    q, k, v = shard(q, "act_heads"), shard(k, "act_kv"), shard(v, "act_kv")

    new_cache = None
    if cache is not None and "paged" in cache:
        # Continuous-batching serve path: ``h`` is a packed stream of T
        # tokens, one a row (S == 1), and ``page_state`` says where each
        # sits in the (B, W) slab the paged kernel takes (``slab_index``).
        # Each token writes its KV at its own (slot, position); a padding
        # row writes to the trash block.  q goes out to the slab, and the
        # kernel's output comes back to the T rows before ``wo``.
        bs = page_state["block_size"]
        table, lens, q_lens = page_state["table"], page_state["lens"], page_state["q_lens"]
        flat, inv = page_state["flat"], page_state["inv"]
        nb = table.shape[0]
        W = inv.shape[0] // nb
        live = (flat < nb * W)[:, None]
        entry = C.paged_update(
            cache["paged"], k, v, positions, table[page_state["slot"]], bs, live
        )
        fill = dict(axis=0, mode="fill", fill_value=0)
        qs = jnp.take(q.reshape(B, H, Dh), inv, **fill).reshape(nb, W, H, Dh)
        if page_state.get("fused"):
            # Fused Pallas kernel: walks the table, streams pages into VMEM
            # tiles, dequantizes int8 in-kernel — no dense gather in HBM.
            from repro.kernels.paged_attention.ops import paged_attention

            o = paged_attention(
                qs, entry, table, lens, q_lens,
                block_size=bs, window=window,
                pages_per_tile=page_state.get("pages_per_tile", 0),
            )
        else:
            # jnp fallback (model-sharded meshes, oracle tests): gather the
            # pages — clamped to the live high-water mark when concrete —
            # then attend with per-slot masks.
            kf, vf = C.paged_gather(entry, table, bs)
            pos2d = lens[:, None] + jnp.arange(W)[None, :]
            o = L.paged_attention(qs, kf, vf, pos2d, window=window)
        o = jnp.take(o.reshape(nb * W, H, Dh), flat, **fill)
        out = shard(o.reshape(B, S, H * Dh), "act_heads_flat") @ ap["wo"]
        return out, {"paged": entry}, None
    if cache is None:
        o = L.blocked_attention(
            q, k, v,
            causal=cfg.causal,
            window=window,
            q_chunk=plan.mha.pu.block_m,
            k_chunk=plan.mha.pu.block_n,
            prefix_len=prefix_len,
        )
        kv_out = (k, v)
    else:
        Sc = cache["k"].shape[1]
        t = cache["t"]  # filled length before this token
        idx = t % Sc if window else jnp.minimum(t, Sc - 1)
        k_cache = lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype), (0, idx, 0, 0))
        v_cache = lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype), (0, idx, 0, 0))
        eff_len = jnp.minimum(t + 1, Sc)
        o = L.decode_attention(q, k_cache, v_cache, eff_len, window=0)
        new_cache = {"k": k_cache, "v": v_cache, "t": t + 1}
        kv_out = None
    out = shard(o.reshape(B, S, H * Dh), "act_heads_flat") @ ap["wo"]
    return out, new_cache, kv_out


def slab_index(slot, pos, lens, q_lens, width: int):
    """Where the tokens of a packed serve stream sit in the (B, W) slab.

    Token t belongs to slot ``slot[t]`` at absolute position ``pos[t]``; it
    is live iff ``lens[b] <= pos[t] < lens[b] + q_lens[b]`` for its slot b
    (the scheduler pads the stream with ``pos = -1``).  Returns ``(flat,
    inv)``: ``flat`` (T,) is each token's slab row ``b * W + pos - lens[b]``
    (B·W on a padding row), ``inv`` (B·W,) the token at each slab row (T on
    a dead row) — so ``take(..., mode="fill")`` moves rows either way and
    fills the dead ones with zeros."""
    B, T = lens.shape[0], slot.shape[0]
    row = pos - lens[slot]
    live = (row >= 0) & (row < q_lens[slot])
    flat = jnp.where(live, slot * width + row, B * width).astype(jnp.int32)
    inv = jnp.full((B * width,), T, jnp.int32).at[flat].set(
        jnp.arange(T, dtype=jnp.int32), mode="drop"
    )
    return flat, inv


def cross_attention_stage(cp: dict, h: jax.Array, memory_kv, cfg: ArchConfig):
    """Decoder -> encoder-memory attention (whisper). memory_kv: (k, v)."""
    B, S, _ = h.shape
    q = (h @ cp["wq"]).reshape(B, S, cfg.n_heads, cfg.d_head)
    mk, mv = memory_kv
    o = L.plain_cross_attention(q, mk, mv)
    return o.reshape(B, S, cfg.n_heads * cfg.d_head) @ cp["wo"]


def cross_kv(cp: dict, memory: jax.Array, cfg: ArchConfig):
    B, Se, _ = memory.shape
    mk = (memory @ cp["wk"]).reshape(B, Se, cfg.n_kv_heads, cfg.d_head)
    mv = (memory @ cp["wv"]).reshape(B, Se, cfg.n_kv_heads, cfg.d_head)
    return mk, mv


# ---------------------------------------------------------------------------
# The EDPU layer
# ---------------------------------------------------------------------------
def edpu_layer(
    lp: dict,
    x: jax.Array,
    *,
    cfg: ArchConfig,
    plan: ExecutionPlan,
    kind: str,
    positions: jax.Array,
    cache: Optional[dict] = None,
    memory: Optional[jax.Array] = None,
    prefix_len: int = 0,
    causal_override: Optional[bool] = None,
    collect: bool = False,
    shard: Callable = Identity,
    page_state: Optional[dict] = None,
):
    """One Encoder/Decoder layer: MHA Stage -> (cross) -> FFN Stage.

    ``collect=True`` (prefill) harvests decode-cache state from the parallel
    pass; the train path keeps it False so no KV leaves the layer scan.
    Returns (x, new_cache, aux_loss)."""
    run_cfg = cfg
    if causal_override is not None:
        import dataclasses

        run_cfg = dataclasses.replace(cfg, causal=causal_override)
    aux = jnp.zeros((), jnp.float32)
    new_cache: dict = {}

    # ---- MHA Stage ---------------------------------------------------------
    h = L.apply_norm(lp["attn"]["ln"], x, cfg.norm)
    if kind in ("attn", "swa", "local"):
        ac = None
        if cache is not None:
            ac = cache if "paged" in cache else cache.get("attn")
        a, nc, kv_out = attention_stage(
            lp["attn"], h,
            cfg=run_cfg, plan=plan, kind=kind, positions=positions,
            cache=ac, prefix_len=prefix_len, shard=shard,
            page_state=page_state,
        )
        if nc is not None:
            if "paged" in nc:
                new_cache["paged"] = nc["paged"]  # keep the pool tree shape
            else:
                new_cache["attn"] = nc
        if cache is None and collect and kv_out is not None:
            new_cache["kv_out"] = kv_out  # harvested by prefill
    elif kind == "rglru":
        a, nc = G.rglru_block(
            lp["attn"], h,
            n_heads=max(cfg.rnn_heads, 1),
            cache=None if cache is None else cache.get("rglru"),
            collect=collect,
        )
        if nc is not None:
            new_cache["rglru"] = nc
    elif kind == "rwkv6":
        a, nc = R.rwkv6_time_mix(
            lp["attn"], h,
            n_heads=cfg.rnn_heads, d_head=cfg.d_head,
            cache=None if cache is None else cache.get("rwkv"),
            collect=collect,
        )
        if nc is not None:
            new_cache["rwkv"] = nc
    else:
        raise ValueError(kind)
    x = shard(x + a, "act_hidden")

    # ---- Cross-attention sub-stage (enc-dec decoder only) -------------------
    if "cross" in lp:
        hc = L.apply_norm(lp["cross"]["ln"], x, cfg.norm)
        if cache is not None and "cross_kv" in cache:
            mkv = cache["cross_kv"]
        else:
            mkv = cross_kv(lp["cross"], memory, cfg)
        x = x + cross_attention_stage(lp["cross"], hc, mkv, cfg)
        if cache is not None or collect:
            new_cache["cross_kv"] = mkv

    # ---- FFN Stage ----------------------------------------------------------
    h2 = L.apply_norm(lp["ffn"]["ln"], x, cfg.norm)
    if cfg.is_moe:
        st = M.MoESettings(
            n_experts=cfg.n_experts,
            top_k=cfg.top_k,
            capacity_factor=cfg.moe_capacity_factor,
            dispatch=plan.moe_dispatch,
        )
        f, aux = M.moe_ffn(lp["ffn"], h2, st, cfg.activation)
    elif kind == "rwkv6":
        f, nc = R.rwkv6_channel_mix(
            lp["ffn"], h2,
            cache=None if cache is None else cache.get("cmix"),
            collect=collect,
        )
        if nc is not None:
            new_cache["cmix"] = nc
    else:
        f = L.mlp(lp["ffn"], h2, cfg.activation)
    x = shard(x + f, "act_hidden")
    return x, (new_cache or None), aux


# ---------------------------------------------------------------------------
# Megatron-SP layer stack (manual collectives; docs/ARCHITECTURE.md
# §"Megatron-SP").  The residual stream is seq-sharded over `model`; each
# stage is one ring gather-matmul up and one reduce-scatter down, so the
# layernorm path lowers with zero all-gather ops.
# ---------------------------------------------------------------------------
def sp_edpu_layer(
    lp: dict,
    x: jax.Array,
    *,
    cfg: ArchConfig,
    plan: ExecutionPlan,
    kind: str,
    positions: jax.Array,
    axis: str = "model",
    n_shards: int = 1,
):
    """One EDPU layer on the sequence-parallel residual.

    ``x`` is this device's (B_local, S/n, d) sequence chunk; weights are the
    local Megatron column/row shards (SP plans force unfused QKV so each
    projection splits on clean head boundaries).  Norms are token-local so
    they run directly on the chunk — the paper's "nonlinear operators
    inserted into the MM dataflow" (C6) costs no communication here.
    """
    ap = lp["attn"]
    Dh = cfg.d_head
    window = (
        cfg.sliding_window
        if kind == "swa"
        else cfg.local_window if kind == "local" else 0
    )

    # ---- MHA Stage: gather(seq) -> local heads -> scatter(seq) ------------
    h = L.apply_norm(ap["ln"], x, cfg.norm)
    wq, wk, wv = ap["wq"], ap["wk"], ap["wv"]
    qkv = L.sp_gather_matmul(
        h, jnp.concatenate([wq, wk, wv], axis=-1), axis, n_shards
    )
    q, k, v = jnp.split(
        qkv, [wq.shape[-1], wq.shape[-1] + wk.shape[-1]], axis=-1
    )
    B, S = q.shape[0], q.shape[1]
    q = q.reshape(B, S, wq.shape[-1] // Dh, Dh)  # local heads H/n
    k = k.reshape(B, S, wk.shape[-1] // Dh, Dh)  # local KV heads KV/n
    v = v.reshape(B, S, wv.shape[-1] // Dh, Dh)
    if cfg.qk_norm:
        q = L.rmsnorm(q, ap["q_norm"])
        k = L.rmsnorm(k, ap["k_norm"])
    if cfg.pos_embedding == "rope":
        q = L.rope(q, positions, cfg.rope_theta)
        k = L.rope(k, positions, cfg.rope_theta)
    o = L.blocked_attention(
        q, k, v,
        causal=False if cfg.encoder_only else cfg.causal,
        window=window,
        q_chunk=plan.mha.pu.block_m,
        k_chunk=plan.mha.pu.block_n,
    )
    o = o.reshape(B, S, o.shape[-2] * Dh)
    x = x + L.sp_scatter_matmul(o, ap["wo"], axis)

    # ---- FFN Stage --------------------------------------------------------
    h2 = L.apply_norm(lp["ffn"]["ln"], x, cfg.norm)
    return x + L.sp_mlp(lp["ffn"], h2, cfg.activation, axis, n_shards)


def sp_stack_forward(
    stack: PyTree,
    x: jax.Array,
    *,
    cfg: ArchConfig,
    plan: ExecutionPlan,
    mesh,
    positions: jax.Array,
    axis: str = "model",
):
    """Run the stacked pattern-groups under shard_map with the residual
    seq-sharded over ``axis`` (Megatron-SP).  In/out spec for ``x`` comes
    from the same ``Shardings`` rules the GSPMD path uses, so entering and
    leaving the manual region needs no resharding."""
    from repro.dist.sharding import Shardings

    n_shards = dict(mesh.shape)[axis]
    pattern = cfg.layer_pattern
    sh = Shardings(mesh, plan, cfg)
    x_spec = sh.act_spec("act_hidden", x.shape)
    stack_specs = sh.stack_specs(stack)

    def body(wl, xl, pos):
        def group(xx, gp):
            for i, kind in enumerate(pattern):
                xx = sp_edpu_layer(
                    gp[i], xx, cfg=cfg, plan=plan, kind=kind,
                    positions=pos, axis=axis, n_shards=n_shards,
                )
            return xx, None

        gb = jax.checkpoint(group) if plan.remat else group
        xl, _ = lax.scan(gb, xl, wl)
        return xl

    return shard_map(
        body,
        mesh=mesh,
        in_specs=(stack_specs, x_spec, PartitionSpec(None, None)),
        out_specs=x_spec,
        check_vma=False,
    )(stack, x, positions)


# ---------------------------------------------------------------------------
# Stacks
# ---------------------------------------------------------------------------
def _run_stack(
    blocks: dict,
    x: jax.Array,
    layer_fn: Callable,
    pattern: tuple[str, ...],
    caches: Optional[dict] = None,
    remat: bool = False,
):
    """Scan the stacked pattern-groups, then the tail layers.

    layer_fn(lp, x, kind, cache) -> (x, new_cache, aux).
    caches mirrors blocks: {"stack": ..., "tail": ...} or None.
    Returns (x, new_caches, total_aux)."""

    def group_body(x, inp):
        gp, gcache = inp
        no_cache = gcache is None or hasattr(gcache, "ndim")  # scan dummy
        new_caches = []
        aux = jnp.zeros((), jnp.float32)
        for i, kind in enumerate(pattern):
            c = None if no_cache else gcache[i]
            x, nc, a = layer_fn(gp[i], x, kind, c)
            new_caches.append(nc)
            aux += a
        return x, (tuple(new_caches), aux)

    body = jax.checkpoint(group_body) if remat else group_body
    new_stack = None
    total_aux = jnp.zeros((), jnp.float32)
    if blocks["stack"] is not None:
        stack_caches = None if caches is None else caches["stack"]
        if stack_caches is None:
            xs = (blocks["stack"], _nones_like_scan(blocks["stack"]))
            x, (new_stack, auxes) = lax.scan(body, x, xs)
        else:
            # The caches ride in the carry and each group's slice is updated
            # in place.  As scanned outputs they would be a second copy of
            # every cache (for the serving engine, the whole KV pool) in HBM.
            def carried(carry, gp):
                x, stack, i = carry
                gcache = jax.tree.map(
                    lambda c: lax.dynamic_index_in_dim(c, i, keepdims=False), stack
                )
                x, (nc, aux) = body(x, (gp, gcache))
                stack = jax.tree.map(
                    lambda c, n: lax.dynamic_update_index_in_dim(c, n, i, 0),
                    stack, nc,
                )
                return (x, stack, i + 1), aux

            (x, new_stack, _), auxes = lax.scan(
                carried, (x, stack_caches, jnp.int32(0)), blocks["stack"]
            )
        total_aux += auxes.sum()
    new_tail = []
    for i, lp in enumerate(blocks["tail"]):
        kind = pattern[i % len(pattern)]
        c = None if caches is None else caches["tail"][i]
        x, nc, a = layer_fn(lp, x, kind, c)
        new_tail.append(nc)
        total_aux += a
    return x, {"stack": new_stack, "tail": tuple(new_tail)}, total_aux


def _nones_like_scan(tree):
    """A scan-compatible 'no cache' placeholder: broadcast None via a dummy."""
    n = jax.tree.leaves(tree)[0].shape[0]
    return jnp.zeros((n, 0))  # zero-width array; treated as falsy cache


def _weight_dtype(params: PyTree):
    """Compute dtype = dtype of the (>=2-D) weight leaves (norms stay fp32)."""
    for leaf in jax.tree.leaves(params):
        if getattr(leaf, "ndim", 0) >= 2:
            return leaf.dtype
    return jnp.bfloat16


def _embed_inputs(params: PyTree, batch: dict, cfg: ArchConfig, cache, dtype):
    """Token/prefix embedding + position injection (shared by the plain,
    sequence-parallel, and pipelined forwards).  Returns (x, positions,
    prefix_len)."""
    x_parts = []
    prefix_len = 0
    if "prefix_embeds" in batch:
        x_parts.append(batch["prefix_embeds"].astype(dtype))
        prefix_len = batch["prefix_embeds"].shape[1]
    if "tokens" in batch and "embed" in params:
        emb = params["embed"].astype(dtype)[batch["tokens"]]
        if cfg.activation == "geglu":  # gemma family scales embeddings
            emb = emb * jnp.asarray(cfg.d_model**0.5, dtype)
        x_parts.append(emb)
    x = x_parts[0] if len(x_parts) == 1 else jnp.concatenate(x_parts, axis=1)
    S = x.shape[1]

    t0 = 0 if cache is None else cache["t"]
    # Per-slot offsets (continuous batching hands a (B,) length vector).
    off = t0[:, None] if getattr(t0, "ndim", 0) == 1 else t0
    positions = off + jnp.arange(S)[None, :]
    if cfg.pos_embedding == "learned":
        x = x + params["pos"].astype(dtype)[None, :S] if cache is None else (
            x + lax.dynamic_slice_in_dim(params["pos"].astype(dtype), t0, 1)[None]
        )
    elif cfg.pos_embedding == "sinusoidal":
        pos = L.sinusoidal_positions(S, cfg.d_model).astype(dtype)
        if cache is None:
            x = x + pos[None]
        else:
            x = x + lax.dynamic_slice_in_dim(
                L.sinusoidal_positions(cfg.max_seq_len, cfg.d_model).astype(dtype),
                t0, 1)[None]
    return x, positions, prefix_len


def forward(
    params: PyTree,
    batch: dict,
    *,
    cfg: ArchConfig,
    plan: ExecutionPlan,
    cache: Optional[PyTree] = None,
    collect_cache: bool = False,
    shard: Callable = Identity,
    mesh=None,
    page_state: Optional[dict] = None,
):
    """Full model forward.

    batch keys (by arch): "tokens" (B,S) int32; optional "prefix_embeds"
    (B,P,d); enc-dec: "enc_embeds" (B,Se,d).  With ``cache`` set, runs one
    decode step (S == 1).  Returns (hidden (B,S,d), new_cache, aux).

    With ``plan.seq_parallel_acts`` and a real ``mesh``, the stacked
    layer-groups run through the Megatron-SP manual-collective path
    (:func:`sp_stack_forward`); everything else stays on the GSPMD path.

    With a *paged* ``cache`` (``models/cache.init_paged_cache``) the pass is
    a continuous-batching serve step over a packed stream: "tokens" is
    (T, 1), ``cache["t"]`` the (T,) absolute position of each token, and
    ``page_state`` holds the block table (B, MB), the per-slot ``lens`` and
    ``q_lens`` (B,), each token's ``slot`` (T,) and the slab map
    :func:`slab_index` derives from them (``serve/engine.make_mixed_step``).
    """
    dtype = _weight_dtype(params)
    x, positions, prefix_len = _embed_inputs(params, batch, cfg, cache, dtype)
    B, S, _ = x.shape
    x = shard(x, "act_hidden")

    # ---- encoder (enc-dec archs) -------------------------------------------
    memory = None
    if cfg.enc_dec:
        if cache is not None and "memory" in cache:
            memory = cache["memory"]
        else:
            enc = batch["enc_embeds"].astype(dtype)
            enc = enc + L.sinusoidal_positions(enc.shape[1], cfg.d_model).astype(dtype)[None]
            enc_positions = jnp.arange(enc.shape[1])[None, :]

            def enc_layer_fn(lp, xx, kind, c):
                return edpu_layer(
                    lp, xx, cfg=cfg, plan=plan, kind=kind,
                    positions=enc_positions, cache=None, prefix_len=0,
                    causal_override=False, shard=shard,
                )

            enc, _, _ = _run_stack(
                params["encoder"], enc, enc_layer_fn, ("attn",), None, plan.remat
            )
            memory = L.apply_norm(params["encoder"]["final_norm"], enc, cfg.norm)

    # ---- decoder / main stack ------------------------------------------------
    def layer_fn(lp, xx, kind, c):
        c = None if (c is None or (hasattr(c, "ndim"))) else c  # scan dummy
        return edpu_layer(
            lp, xx, cfg=cfg, plan=plan, kind=kind, positions=positions,
            cache=c, memory=memory, prefix_len=prefix_len,
            causal_override=False if cfg.encoder_only else None,
            collect=collect_cache, shard=shard, page_state=page_state,
        )

    layer_caches = None if cache is None else cache["layers"]
    use_sp = (
        plan.seq_parallel_acts
        and mesh is not None
        and cache is None
        and not collect_cache
        and prefix_len == 0
        and params["blocks"]["stack"] is not None
    )
    if use_sp:
        x = sp_stack_forward(
            params["blocks"]["stack"], x, cfg=cfg, plan=plan, mesh=mesh,
            positions=positions,
        )
        # tail layers (if any) stay on the GSPMD path
        x, new_layer_caches, aux = _run_stack(
            {"stack": None, "tail": params["blocks"]["tail"]}, x, layer_fn,
            cfg.layer_pattern, None, plan.remat,
        )
        new_layer_caches = None
    else:
        x, new_layer_caches, aux = _run_stack(
            params["blocks"], x, layer_fn, cfg.layer_pattern, layer_caches,
            plan.remat,
        )
    x = L.apply_norm(params["final_norm"], x, cfg.norm)

    new_cache = None
    if cache is not None:
        new_cache = dict(cache)
        new_cache["layers"] = new_layer_caches
        new_cache["t"] = cache["t"] + S
    elif collect_cache:
        new_cache = {"layers": new_layer_caches, "t": S}
        if memory is not None:
            new_cache["memory"] = memory
    return x, new_cache, aux


# ---------------------------------------------------------------------------
# Heads + losses
# ---------------------------------------------------------------------------
def logits_fn(params: PyTree, x: jax.Array, cfg: ArchConfig):
    if cfg.n_classes:
        return x.mean(axis=1) @ params["cls_head"]
    w = params.get("lm_head")
    if w is None:
        w = params["embed"].T
    return x @ w


def chunked_softmax_xent(
    x: jax.Array,
    w: jax.Array,
    targets: jax.Array,
    loss_mask: Optional[jax.Array] = None,
    chunk: int = 512,
):
    """Cross-entropy without materializing full (B,S,V) logits.

    x: (B,S,d); w: (d,V); targets: (B,S) int32. Returns (sum_loss, n_tokens)."""
    B, S, d = x.shape
    c = min(chunk, S)
    while S % c:
        c //= 2
    N = S // c
    xr = x.reshape(B, N, c, d).swapaxes(0, 1)
    tr = targets.reshape(B, N, c).swapaxes(0, 1)
    if loss_mask is None:
        mr = jnp.ones((N, B, c), jnp.float32)
    else:
        mr = loss_mask.reshape(B, N, c).swapaxes(0, 1).astype(jnp.float32)

    # checkpoint: without it the scan saves every chunk's (B, c, V) logits
    # for the backward pass — 40 GB/chip at a 152k vocab.  Recompute instead.
    @jax.checkpoint
    def step(acc, inp):
        xc, tc, mc = inp
        logits = (xc @ w).astype(jnp.float32)  # (B, c, V)
        lse = jax.nn.logsumexp(logits, axis=-1)
        tl = jnp.take_along_axis(logits, tc[..., None], axis=-1)[..., 0]
        loss = (lse - tl) * mc
        return (acc[0] + loss.sum(), acc[1] + mc.sum()), None

    (total, n), _ = lax.scan(step, (jnp.zeros(()), jnp.zeros(())), (xr, tr, mr))
    return total, jnp.maximum(n, 1.0)


def _head_loss(params: PyTree, x: jax.Array, batch: dict, cfg: ArchConfig,
               aux: jax.Array):
    """Loss from final hidden states (shared by every forward variant)."""
    if cfg.n_classes:  # classifier head (ViT)
        logits = logits_fn(params, x, cfg).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        tl = jnp.take_along_axis(logits, batch["label"][:, None], axis=-1)[:, 0]
        return (lse - tl).mean() + 0.01 * aux
    w = params.get("lm_head", None)
    if w is None:
        w = params["embed"].T.astype(x.dtype)
    targets = batch["targets"]
    prefix = batch.get("prefix_embeds")
    if prefix is not None:
        # loss only over the text positions (prefix carries no targets)
        P = prefix.shape[1]
        x = x[:, P:]
    total, n = chunked_softmax_xent(x, w, targets, batch.get("loss_mask"))
    return total / n + 0.01 * aux


def lm_loss(params: PyTree, batch: dict, *, cfg: ArchConfig, plan: ExecutionPlan,
            shard: Callable = Identity, mesh=None):
    x, _, aux = forward(params, batch, cfg=cfg, plan=plan, shard=shard, mesh=mesh)
    return _head_loss(params, x, batch, cfg, aux)


def check_pipeline_supported(cfg: ArchConfig, plan: ExecutionPlan, batch: int):
    """Raise with the first reason a pod_role="pipeline" plan cannot route
    through pipeline_lm_loss; return (n_stage, n_micro) when it can."""
    n_stage = plan.pod_axis
    n_micro = plan.microbatches
    reasons = []
    if n_stage <= 1:
        reasons.append("pod axis has a single stage")
    if plan.model_axis > 1:
        # pipeline_forward's weight in_specs are P("pod", ...) only: a >1
        # model axis would gather the TP weight shards every step and
        # duplicate the stage compute across it
        reasons.append(
            f"model axis {plan.model_axis} > 1 (pipeline composes with DP, "
            "not TP; put the spare devices on 'data')"
        )
    if cfg.is_moe:
        reasons.append("MoE aux losses do not cross stage boundaries yet")
    if cfg.enc_dec or cfg.frontend != "none":
        reasons.append("enc-dec/frontends keep non-stack state")
    if batch % max(n_micro, 1):
        reasons.append(f"batch {batch} not divisible by microbatches {n_micro}")
    elif (batch // max(n_micro, 1)) % max(plan.data_axis, 1):
        # replication across DP replicas (measured 21x FLOPs waste) must
        # fail loudly, never run silently
        reasons.append(
            f"microbatch {batch // max(n_micro, 1)} does not fold over "
            f"data axis {plan.data_axis}"
        )
    if n_micro < n_stage:
        reasons.append(f"microbatches {n_micro} < stages {n_stage}")
    if reasons:
        raise ValueError(
            "pod_role='pipeline' plan cannot execute: " + "; ".join(reasons)
        )
    return n_stage, n_micro


def pipeline_lm_loss(params: PyTree, batch: dict, *, cfg: ArchConfig,
                     plan: ExecutionPlan, mesh, shard: Callable = Identity):
    """LM loss with the stacked layer-groups run as pipeline stages over the
    ``pod`` axis (dist.pipeline.pipeline_forward; docs/ARCHITECTURE.md
    §"Pod axis").

    Embedding, tail layers, final norm, and the loss head run on the GSPMD
    path (replicated over ``pod``); the stack weights are sliced per stage
    (``Shardings.param_spec`` puts ``pod`` on the stacked leading dim) and
    microbatches flow stage-to-stage via collective-permute.  Numerically
    identical to the data-parallel baseline: the same layers run on the
    same tokens, only the schedule changes.
    """
    from repro.dist.pipeline import pipeline_forward
    from repro.dist.sharding import Shardings

    dtype = _weight_dtype(params)
    x, positions, prefix_len = _embed_inputs(params, batch, cfg, None, dtype)
    B, S, D = x.shape
    n_stage, n_micro = check_pipeline_supported(cfg, plan, B)
    stack = params["blocks"]["stack"]
    n_groups = jax.tree.leaves(stack)[0].shape[0]
    if n_groups % n_stage:
        raise ValueError(
            f"{n_groups} stacked layer-groups do not split into "
            f"{n_stage} pipeline stages"
        )
    x = shard(x, "act_hidden")
    micro = x.reshape(n_micro, B // n_micro, S, D)

    sh = Shardings(mesh, plan, cfg)
    batch_axes = sh.batch_axes_for(B // n_micro) or ()
    pattern = cfg.layer_pattern

    def stage_fn(wl, xm):
        # positions recomputed from the microbatch shape: shard_map (inside
        # pipeline_forward) must not close over traced arrays.
        pos = jnp.arange(xm.shape[1])[None, :]

        def group(xx, gp):
            for i, kind in enumerate(pattern):
                xx, _, _ = edpu_layer(
                    gp[i], xx, cfg=cfg, plan=plan, kind=kind,
                    positions=pos, prefix_len=prefix_len,
                    causal_override=False if cfg.encoder_only else None,
                )
            return xx, None

        gb = jax.checkpoint(group) if plan.remat else group
        xm, _ = lax.scan(gb, xm, wl)
        return xm

    pp = pipeline_forward(stage_fn, mesh, axis="pod", batch_axes=tuple(batch_axes))
    x = pp(stack, micro).reshape(B, S, D)

    # tail layers reuse the shared stack runner (same as the SP branch)
    def layer_fn(lp, xx, kind, c):
        return edpu_layer(
            lp, xx, cfg=cfg, plan=plan, kind=kind, positions=positions,
            prefix_len=prefix_len,
            causal_override=False if cfg.encoder_only else None, shard=shard,
        )

    x, _, aux = _run_stack(
        {"stack": None, "tail": params["blocks"]["tail"]}, x, layer_fn,
        pattern, None, plan.remat,
    )
    x = L.apply_norm(params["final_norm"], x, cfg.norm)
    return _head_loss(params, x, batch, cfg, aux)
