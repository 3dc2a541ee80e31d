"""Decode caches.

Per layer kind:
  attn/swa/local : {"k","v": (B, Sc, KV, Dh), "t": ()}   Sc = window for
                   windowed layers (ring buffer; softmax is permutation-
                   invariant over kv so ring order is free), else cache_len.
  rglru          : {"h": (B, W), "conv": (B, cw-1, W)}
  rwkv6          : {"rwkv": {"S": (B,H,D,D), "shift": (B,d)}, "cmix": (B,d)}
Enc-dec adds {"memory": (B, Se, d)} and per-decoder-layer {"cross_kv"}.

The cache tree mirrors params ({"stack": ..., "tail": ...}) so the layer scan
threads it.  ``init_cache`` builds zero caches (or ShapeDtypeStructs under
``jax.eval_shape`` for the dry-run); ``cache_from_prefill`` turns a
collect_cache=True forward pass into a decode-ready cache.
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.core.plan import ExecutionPlan, int8_scale_lanes

PyTree = Any


def _layer_cache(cfg: ArchConfig, kind: str, B: int, cache_len: int, dtype):
    KV, Dh, d = cfg.n_kv_heads, cfg.d_head, cfg.d_model
    entry: dict = {}
    if kind in ("attn", "swa", "local"):
        window = (
            cfg.sliding_window
            if kind == "swa"
            else cfg.local_window if kind == "local" else 0
        )
        Sc = min(window, cache_len) if window else cache_len
        entry["attn"] = {
            "k": jnp.zeros((B, Sc, KV, Dh), dtype),
            "v": jnp.zeros((B, Sc, KV, Dh), dtype),
            "t": jnp.zeros((), jnp.int32),
        }
    elif kind == "rglru":
        W = cfg.lru_width or cfg.d_model
        entry["rglru"] = {
            "h": jnp.zeros((B, W), jnp.float32),
            "conv": jnp.zeros((B, cfg.conv_width - 1, W), jnp.float32),
        }
    elif kind == "rwkv6":
        H = cfg.rnn_heads
        entry["rwkv"] = {
            "S": jnp.zeros((B, H, Dh, Dh), jnp.float32),
            "shift": jnp.zeros((B, d), jnp.float32),
        }
        entry["cmix"] = jnp.zeros((B, d), jnp.float32)
    if cfg.enc_dec:
        entry["cross_kv"] = (
            jnp.zeros((B, cfg.enc_seq, KV, Dh), dtype),
            jnp.zeros((B, cfg.enc_seq, KV, Dh), dtype),
        )
    return entry


def init_cache(
    cfg: ArchConfig,
    plan: ExecutionPlan,
    batch_size: int,
    cache_len: int,
    dtype=jnp.bfloat16,
) -> PyTree:
    pattern = cfg.layer_pattern
    n_full, rem = divmod(cfg.n_layers, len(pattern))

    def group(_):
        return tuple(
            _layer_cache(cfg, kind, batch_size, cache_len, dtype) for kind in pattern
        )

    groups = [group(i) for i in range(n_full)]
    stack = jax.tree.map(lambda *xs: jnp.stack(xs), *groups) if n_full else None
    tail = tuple(
        _layer_cache(cfg, pattern[i], batch_size, cache_len, dtype)
        for i in range(rem)
    )
    cache: dict = {
        "layers": {"stack": stack, "tail": tail},
        "t": jnp.zeros((), jnp.int32),
    }
    if cfg.enc_dec:
        cache["memory"] = jnp.zeros((batch_size, cfg.enc_seq, cfg.d_model), dtype)
    return cache


# ---------------------------------------------------------------------------
# Paged KV cache (continuous-batching serving; docs/ARCHITECTURE.md §Serving).
#
# One shared pool of fixed-size blocks per attention layer; a request owns a
# set of blocks through its block-table row (position p of slot b lives at
# flat pool slot ``table[b, p // bs] * bs + p % bs``).  Block 0 is the trash
# block — idle decode slots point their whole table at it, so the jitted step
# keeps static shapes with no per-request branching.  The int8 page option
# reuses ``train/compression.quantize`` on a per-(token, kv-head) grid (the
# paper's Int8 deployment precision applied to the cache).
#
# Rollback invariant (speculative decoding rides on this): pages past a
# slot's per-slot length hold arbitrary stale KV — rejected draft rows,
# leftovers from a block's previous owner — and both attention paths mask
# by the length vector, never by page contents.  Rolling a slot back past
# rejected positions is therefore just shrinking its length: the block
# table keeps the blocks, and the next ``paged_update`` at those positions
# overwrites the stale rows in place.
# ---------------------------------------------------------------------------
def _kv_vec_scale(x: jax.Array) -> jax.Array:
    """Int8 grid per (token, kv-head) vector: max |x| over d_head / 127."""
    m = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return jnp.maximum(m, 1e-12) / 127.0


def _paged_layer_entry(cfg: ArchConfig, serve) -> dict:
    KV, Dh = cfg.n_kv_heads, cfg.d_head
    N, bs = serve.n_blocks, serve.block_size
    if serve.kv_dtype == "int8":
        P = int8_scale_lanes(KV, bs)
        return {
            "paged": {
                "k": jnp.zeros((N, bs, KV, Dh), jnp.int8),
                "v": jnp.zeros((N, bs, KV, Dh), jnp.int8),
                # one scale per (token, head) at lane i * KV + h of the
                # page's row: the kernel fetches a page's scales as one
                # lane-aligned (1, P) DMA
                "k_scale": jnp.zeros((N, 1, P), jnp.float32),
                "v_scale": jnp.zeros((N, 1, P), jnp.float32),
            }
        }
    dt = {"bf16": jnp.bfloat16, "fp32": jnp.float32}[serve.kv_dtype]
    return {
        "paged": {
            "k": jnp.zeros((N, bs, KV, Dh), dt),
            "v": jnp.zeros((N, bs, KV, Dh), dt),
        }
    }


def init_paged_cache(cfg: ArchConfig, plan: ExecutionPlan, serve) -> PyTree:
    """Zero block pools mirroring the layer tree ({"stack": ..., "tail": ...}).

    ``serve`` is a :class:`repro.core.plan.ServePlan`.  Only attention-kind
    layers are supported (``serve_feasible`` gates the rest)."""
    pattern = cfg.layer_pattern
    n_full, rem = divmod(cfg.n_layers, len(pattern))
    groups = [
        tuple(_paged_layer_entry(cfg, serve) for _ in pattern) for _ in range(n_full)
    ]
    stack = jax.tree.map(lambda *xs: jnp.stack(xs), *groups) if n_full else None
    tail = tuple(_paged_layer_entry(cfg, serve) for _ in range(rem))
    return {"layers": {"stack": stack, "tail": tail}}


def paged_copy_block(pools: PyTree, src, dst) -> PyTree:
    """Copy one block's pages ``src -> dst`` across every layer pool.

    The copy-on-write fork of prefix sharing (docs/ARCHITECTURE.md §"Prefix
    sharing"): when a new request diverges *inside* a resident shared block,
    the scheduler allocates it a fresh block and the engine duplicates the
    matched pages there before its next step — the resident block is never
    written by a sharer.  Copies every leaf (k/v and, for int8 pools, the
    quantization scales), so the fork is byte-identical by construction.

    ``src``/``dst`` may be traced scalars: callers jit this once and reuse
    it for every fork (block ids are data, not shapes)."""
    src = jnp.asarray(src, jnp.int32)
    dst = jnp.asarray(dst, jnp.int32)
    layers = pools["layers"]
    stack = (
        jax.tree.map(lambda x: x.at[:, dst].set(x[:, src]), layers["stack"])
        if layers["stack"] is not None
        else None
    )
    tail = jax.tree.map(lambda x: x.at[dst].set(x[src]), layers["tail"])
    return {"layers": {"stack": stack, "tail": tail}}


def paged_flat_slots(
    table: jax.Array,
    positions: jax.Array,
    block_size: int,
    valid: Optional[jax.Array] = None,
):
    """Flat pool slots for ``positions`` (B, S) under block table (B, MB).

    ``valid`` (B, S) bool routes masked positions to the trash block (block
    0), which is how the unified mixed step keeps static shapes: a decode
    slot's unused slab rows and an idle slot's whole row write there.
    Positions are clamped into the table extent first (a dead row's
    position may run past ``max_seq_len``)."""
    B, MB = table.shape
    pos = jnp.clip(positions, 0, MB * block_size - 1)
    blk = table[jnp.arange(B)[:, None], pos // block_size]
    if valid is not None:
        blk = jnp.where(valid, blk, 0)
    return blk * block_size + pos % block_size


def paged_update(
    entry: dict, k: jax.Array, v: jax.Array, positions: jax.Array,
    table: jax.Array, block_size: int, valid: Optional[jax.Array] = None,
) -> dict:
    """Write new (B, S, KV, Dh) keys/values at their slots; returns the entry.

    Slot collisions only happen on the trash block (idle slots and, with
    ``valid`` given, the dead rows of a mixed slab), where any winner is
    fine — live requests own disjoint blocks by construction."""
    from repro.train.compression import quantize

    B, S = k.shape[:2]
    flat = paged_flat_slots(table, positions, block_size, valid).reshape(-1)

    def put(pool, val):
        fp = pool.reshape((-1,) + pool.shape[2:])
        fp = fp.at[flat].set(val.reshape((B * S,) + val.shape[2:]).astype(fp.dtype))
        return fp.reshape(pool.shape)

    def put_scale(pool, val):  # (N, 1, P) pool, (B, S, KV, 1) scales
        KV = val.shape[2]
        lane = (flat % block_size)[:, None] * KV + jnp.arange(KV)[None, :]
        return pool.at[(flat // block_size)[:, None], 0, lane].set(
            val.reshape(B * S, KV)
        )

    out = dict(entry)
    if "k_scale" in entry:
        qk, sk = quantize(k.astype(jnp.float32), "int8", _kv_vec_scale(k))
        qv, sv = quantize(v.astype(jnp.float32), "int8", _kv_vec_scale(v))
        out["k"] = put(entry["k"], qk)
        out["v"] = put(entry["v"], qv)
        out["k_scale"] = put_scale(entry["k_scale"], sk)
        out["v_scale"] = put_scale(entry["v_scale"], sv)
    else:
        out["k"] = put(entry["k"], k)
        out["v"] = put(entry["v"], v)
    return out


def paged_gather(
    entry: dict,
    table: jax.Array,
    block_size: int,
    max_blocks: Optional[int] = None,
):
    """Materialize each slot's pages in position order: (B, L*bs, KV, Dh).

    Key j of the gathered view sits at sequence position j, so the attention
    mask is just ``j <= q_position`` — the block indirection vanishes here.
    This is the fallback/oracle path; the production serve step runs
    ``kernels/paged_attention`` which consumes the table directly and never
    materializes this buffer.

    ``L`` is clamped to the live blocks' high-water mark instead of always
    the full table width: block tables are prefix-dense (a slot's blocks
    occupy its leading columns), so every live position sits below the last
    non-trash column and the tail of the table gathers nothing but trash.
    The clamp is automatic when ``table`` is concrete (eager tests, the
    interpreter path); under a jit trace the width is static, so callers
    pass ``max_blocks`` themselves or get the full extent."""
    from repro.train.compression import dequantize

    MB = table.shape[1]
    if max_blocks is None and not isinstance(table, jax.core.Tracer):
        live = np.nonzero(np.asarray(table).any(axis=0))[0]
        max_blocks = int(live[-1]) + 1 if live.size else 1
    L = min(MB, max_blocks) if max_blocks else MB
    pos = jnp.arange(L * block_size)
    blk = table[:, pos // block_size]
    flat = blk * block_size + pos % block_size  # (B, MB*bs)

    def take(pool):
        return pool.reshape((-1,) + pool.shape[2:])[flat]

    def take_scale(pool):  # (N, 1, P) -> (B, L*bs, KV, 1)
        KV = entry["k"].shape[2]
        lane = (flat % block_size)[..., None] * KV + jnp.arange(KV)
        return pool[(flat // block_size)[..., None], 0, lane][..., None]

    k, v = take(entry["k"]), take(entry["v"])
    if "k_scale" in entry:
        k = dequantize(k, take_scale(entry["k_scale"]), "int8")
        v = dequantize(v, take_scale(entry["v_scale"]), "int8")
    return k, v


def _kv_to_ring(k: jax.Array, v: jax.Array, Sc: int, dtype):
    """Place a prefill's (B,S,KV,D) kv into an Sc-slot cache at slot = pos % Sc."""
    B, S, KV, Dh = k.shape
    if S <= Sc:
        pad = [(0, 0), (0, Sc - S), (0, 0), (0, 0)]
        return jnp.pad(k, pad).astype(dtype), jnp.pad(v, pad).astype(dtype)
    keep = jnp.arange(S - Sc, S)
    slots = keep % Sc
    kk = jnp.zeros((B, Sc, KV, Dh), dtype).at[:, slots].set(
        k[:, keep].astype(dtype)
    )
    vv = jnp.zeros((B, Sc, KV, Dh), dtype).at[:, slots].set(
        v[:, keep].astype(dtype)
    )
    return kk, vv


def cache_from_prefill(
    cfg: ArchConfig,
    plan: ExecutionPlan,
    prefill_cache: PyTree,
    cache_len: int,
    dtype=jnp.bfloat16,
) -> PyTree:
    """Convert the collect_cache=True output of ``forward`` into a decode cache.

    Call outside jit (the prefill length is read as a python int)."""
    S = int(prefill_cache["t"])
    pattern = cfg.layer_pattern
    layers = prefill_cache["layers"]

    def convert_entry(entry, kind):
        e = dict(entry)
        if "kv_out" in e:
            k, v = e.pop("kv_out")
            window = (
                cfg.sliding_window
                if kind == "swa"
                else cfg.local_window if kind == "local" else 0
            )
            Sc = min(window, cache_len) if window else cache_len
            kk, vv = _kv_to_ring(k, v, Sc, dtype)
            e["attn"] = {"k": kk, "v": vv, "t": jnp.asarray(S, jnp.int32)}
        return e

    new_stack = None
    if layers["stack"] is not None:
        new_stack = _convert_stacked(layers["stack"], pattern, cfg, cache_len, S, dtype)
    new_tail = tuple(
        convert_entry(layers["tail"][i], pattern[i % len(pattern)])
        for i in range(len(layers["tail"]))
    )
    cache = {
        "layers": {"stack": new_stack, "tail": new_tail},
        "t": jnp.asarray(S, jnp.int32),
    }
    if "memory" in prefill_cache:
        cache["memory"] = prefill_cache["memory"]
    return cache


def _convert_stacked(stack, pattern, cfg, cache_len, S, dtype):
    out = []
    for i, kind in enumerate(pattern):
        entry = dict(stack[i])
        if "kv_out" in entry:
            k, v = entry.pop("kv_out")  # (n_groups, B, S, KV, Dh)
            window = (
                cfg.sliding_window
                if kind == "swa"
                else cfg.local_window if kind == "local" else 0
            )
            Sc = min(window, cache_len) if window else cache_len
            kk, vv = jax.vmap(lambda a, b: _kv_to_ring(a, b, Sc, dtype))(k, v)
            n = k.shape[0]
            entry["attn"] = {
                "k": kk,
                "v": vv,
                "t": jnp.full((n,), S, jnp.int32),
            }
        out.append(entry)
    return tuple(out)
