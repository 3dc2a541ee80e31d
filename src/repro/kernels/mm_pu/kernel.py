"""MM PU Pallas kernel — the AIE MM PU (paper §IV.B) as a VMEM-tiled matmul.

Block shapes come from the CAT tile solver (core/pu.py, Eq. 3'/4'; equation
cross-reference: docs/ARCHITECTURE.md): the tile
family LARGE/STANDARD/SMALL is the paper's Fig. 4 on TPU.  The epilogue
(bias / activation / residual / int8 dequant) is the paper's C6: memory-bound
nonlinear ops ride the MM dataflow instead of round-tripping HBM.

Grid (M/bm, N/bn, K/bk), k innermost; fp32 accumulation in VMEM scratch;
double buffering of the HBM->VMEM streams is Pallas' pipeline (the AIE
DMA/Window analog, C7).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import kernels


def _apply_activation(x, activation: str):
    if activation == "gelu":
        return jax.nn.gelu(x, approximate=True)
    if activation == "silu":
        return jax.nn.silu(x)
    if activation == "relu":
        return jax.nn.relu(x)
    if activation == "relu2":
        return jnp.square(jax.nn.relu(x))
    return x


def _mm_kernel(
    x_ref, w_ref, *rest, nk: int, activation: str, has_bias: bool,
    has_residual: bool, int8_w: bool
):
    idx = 0
    scale_ref = rest[idx] if int8_w else None
    idx += int(int8_w)
    bias_ref = rest[idx] if has_bias else None
    idx += int(has_bias)
    res_ref = rest[idx] if has_residual else None
    idx += int(has_residual)
    o_ref = rest[idx]
    acc_ref = rest[idx + 1]

    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]
    w = w_ref[...]
    if int8_w:
        w = w.astype(jnp.float32)
    acc_ref[...] += jnp.dot(
        x.astype(jnp.float32) if int8_w else x,
        w,
        preferred_element_type=jnp.float32,
    )

    @pl.when(k == nk - 1)
    def _epilogue():
        r = acc_ref[...]
        if int8_w:
            r = r * scale_ref[...].astype(jnp.float32)  # per-column dequant
        if has_bias:
            r = r + bias_ref[...].astype(jnp.float32)
        r = _apply_activation(r, activation)
        if has_residual:
            r = r + res_ref[...].astype(jnp.float32)
        o_ref[...] = r.astype(o_ref.dtype)


def mm_pu_call(
    x: jax.Array,
    w: jax.Array,
    *,
    block_m: int,
    block_n: int,
    block_k: int,
    bias=None,
    residual=None,
    w_scale=None,
    activation: str = "none",
    out_dtype=None,
):
    """x: (M, K); w: (K, N) [int8 if w_scale given]; bias: (1, N);
    residual: (M, N); w_scale: (1, N). Dims must be multiples of the blocks
    (ops.py pads — the paper's ViT L=197 padding observation)."""
    M, K = x.shape
    K2, N = w.shape
    assert K == K2, (x.shape, w.shape)
    assert M % block_m == 0 and N % block_n == 0 and K % block_k == 0
    nk = K // block_k
    int8_w = w_scale is not None
    out_dtype = out_dtype or x.dtype

    in_specs = [
        pl.BlockSpec((block_m, block_k), lambda i, j, k: (i, k)),
        pl.BlockSpec((block_k, block_n), lambda i, j, k: (k, j)),
    ]
    args = [x, w]
    if int8_w:
        in_specs.append(pl.BlockSpec((1, block_n), lambda i, j, k: (0, j)))
        args.append(w_scale)
    if bias is not None:
        in_specs.append(pl.BlockSpec((1, block_n), lambda i, j, k: (0, j)))
        args.append(bias)
    if residual is not None:
        in_specs.append(pl.BlockSpec((block_m, block_n), lambda i, j, k: (i, j)))
        args.append(residual)

    kernel = functools.partial(
        _mm_kernel,
        nk=nk,
        activation=activation,
        has_bias=bias is not None,
        has_residual=residual is not None,
        int8_w=int8_w,
    )
    scratch = [pltpu.VMEM((block_m, block_n), jnp.float32)]
    return pl.pallas_call(
        kernel,
        grid=(M // block_m, N // block_n, nk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=scratch,
        interpret=kernels.interpret_mode(),
    )(*args)
