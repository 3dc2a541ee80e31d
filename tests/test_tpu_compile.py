"""Compile-only checks against a described TPU v5e: what the Pallas
interpreter cannot show.

The paged-attention kernel and the engine's unified step are compiled by
Mosaic/XLA:TPU at qwen3-1.7b widths, with the knobs ``derive_serve_plan``
derives for one v5e, from shapes alone — nothing runs.  Mosaic refuses
what interpret mode accepts (slices not aligned to the vreg tiling, more
VMEM than a kernel may use), and XLA:TPU refuses a step that does not fit
the chip's HBM.  The topology is described inside a fixture, so only the
worker that runs this file loads the TPU compiler.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import kernels
from repro.configs import get_config
from repro.core.hardware import TPU_V5E
from repro.core.plan import (
    SERVE_WEIGHT_DTYPE,
    derive_plan,
    derive_serve_plan,
    int8_scale_lanes,
)

ARCH = "qwen3-1.7b"
MESH1 = {"data": 1, "model": 1}


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    # programs traced for the described chip must not be served from the
    # trace cache to a later CPU test of the same shapes
    jax.clear_caches()


@pytest.fixture
def compiled_kernels(monkeypatch):
    """Steer the kernels to Mosaic although the backend here is the CPU."""
    monkeypatch.setattr(kernels, "interpret_mode", lambda: False)
    yield
    jax.clear_caches()


def _names_kernel(hlo: str) -> bool:
    """A Mosaic custom call in ``hlo`` carries the kernel's own name."""
    from repro.kernels.paged_attention.kernel import NAME

    return any("tpu_custom_call" in line and f"/{NAME}/pallas_call" in line
               for line in hlo.splitlines())


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_kernel_mode_interprets_on_cpu():
    assert jax.default_backend() == "cpu"
    assert kernels.interpret_mode() is True


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
@pytest.mark.parametrize("slab", ["decode", "plan"])
def test_paged_attention_compiles_at_derived_tile(
    one_chip, compiled_kernels, kv_dtype, slab
):
    """The fused kernel at the derived pages_per_tile, for a width-1 slab
    (every decode step and the rolled loop) and the plan's mixed slab:
    Mosaic compiles it within the scoped VMEM it asks for, which stays
    inside half the chip's VMEM, the planner's budget, with its margin."""
    from repro.kernels.paged_attention.kernel import vmem_bytes, vmem_limit
    from repro.kernels.paged_attention.ops import paged_attention

    cfg = get_config(ARCH)
    serve = derive_serve_plan(cfg, MESH1, TPU_V5E, kv_dtype=kv_dtype)
    B, W, MB = serve.decode_batch, serve.mixed_slab_width, serve.max_blocks_per_seq
    if slab == "decode":
        W = 1
    N, bs, KH, D = serve.n_blocks, serve.block_size, cfg.n_kv_heads, cfg.d_head
    S = lambda shape, dt: _sds(one_chip, shape, dt)
    if kv_dtype == "int8":
        P = int8_scale_lanes(KH, bs)
        entry = {
            "k": S((N, bs, KH, D), jnp.int8), "v": S((N, bs, KH, D), jnp.int8),
            "k_scale": S((N, 1, P), jnp.float32), "v_scale": S((N, 1, P), jnp.float32),
        }
    else:
        entry = {"k": S((N, bs, KH, D), jnp.bfloat16), "v": S((N, bs, KH, D), jnp.bfloat16)}
    q = S((B, W, cfg.n_heads, D), jnp.float32)
    shape = dict(
        kv_dtype=kv_dtype, q_itemsize=4, kv_heads=KH, rows=cfg.n_heads // KH * W,
        head_dim=D, block_size=bs, pages_per_tile=serve.pages_per_tile,
    )
    assert serve.pages_per_tile * bs >= 128
    assert vmem_bytes(**shape) <= TPU_V5E.vmem_bytes // 2
    assert vmem_limit(**shape) <= TPU_V5E.vmem_bytes * 5 // 8
    fn = jax.jit(
        lambda q, e, t, l, ql: paged_attention(
            q, e, t, l, ql, block_size=bs, pages_per_tile=serve.pages_per_tile
        )
    )
    compiled = fn.lower(
        q, entry, S((B, MB), jnp.int32), S((B,), jnp.int32), S((B,), jnp.int32)
    ).compile()
    assert _names_kernel(compiled.as_text())


def test_unified_step_fits_one_chip(one_chip, compiled_kernels):
    """The engine's mixed step at the derived plan, on its packed stream of
    ``step_tokens`` rows: Mosaic kernel inside, and weights + KV pool + the
    step's working set within the HBM that XLA:TPU gives a program (it
    raises RESOURCE_EXHAUSTED otherwise).  The temporaries are XLA:TPU's
    bf16 copies of the f32 layer weights (hoisted out of the layer loop,
    2 B a non-embedding parameter) and the step's working set, which
    follows T: 0.23 GB past the copies, against 0.54 GB for the whole
    (B, W) slab."""
    from repro.models.cache import init_paged_cache
    from repro.models.params import init_params
    from repro.serve.engine import make_mixed_step

    cfg = get_config(ARCH)
    plan = derive_plan(cfg, MESH1, TPU_V5E, batch=4, seq_len=128, training=False)
    serve = derive_serve_plan(cfg, MESH1, TPU_V5E)

    def place(tree):
        return jax.tree.map(lambda x: _sds(one_chip, x.shape, x.dtype), tree)

    params = place(jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg, plan, dtype=SERVE_WEIGHT_DTYPE)))
    pools = place(jax.eval_shape(lambda: init_paged_cache(cfg, plan, serve)))
    B, T, MB = serve.decode_batch, serve.step_tokens, serve.max_blocks_per_seq
    assert T == 1152
    S = lambda shape, dt: _sds(one_chip, shape, dt)
    step = make_mixed_step(cfg, plan, serve, fused=True)
    compiled = step.lower(
        params, pools, S((3, T), jnp.int32), S((B, MB), jnp.int32),
        S((B,), jnp.int32), S((B,), jnp.int32), S((B,), jnp.float32),
    ).compile()
    hlo = compiled.as_text()
    assert _names_kernel(hlo)
    # the paged kernel is the step's only custom call
    assert sum("tpu_custom_call" in line and "custom-call(" in line
               for line in hlo.splitlines()) == 1
    m = compiled.memory_analysis()
    bf16_copies = 2 * (cfg.param_count() - cfg.vocab_size * cfg.d_model)
    assert m.temp_size_in_bytes - bf16_copies < 0.3e9
    # the donated pools come back in place: only the sampled tokens are new
    assert m.output_size_in_bytes - m.alias_size_in_bytes < 2**20
    # 15.75 of the chip's 16 GiB are the program's
    assert m.argument_size_in_bytes + m.temp_size_in_bytes <= TPU_V5E.hbm_bytes - 2**28
