import jax
import jax.numpy as jnp
import pytest

# Smoke tests and benches see the real (single) device; ONLY the dry-run
# sets xla_force_host_platform_device_count (in its own process).

try:  # real hypothesis when installed (CI); deterministic stub otherwise
    import hypothesis  # noqa: F401
except ImportError:
    from _hypothesis_stub import install as _install_hypothesis_stub

    _install_hypothesis_stub()


def assert_traces_bounded(trace_counts: dict) -> None:
    """The serving engine's no-retrace contract
    (``repro.serve.engine.traces_bounded``)."""
    from repro.serve.engine import traces_bounded

    assert traces_bounded(trace_counts), trace_counts


@pytest.fixture(scope="session")
def key():
    return jax.random.PRNGKey(0)


@pytest.fixture(scope="session")
def mesh1():
    from repro.launch.mesh import make_host_mesh

    return make_host_mesh()


def make_batch(cfg, key, B=2, S=16, dtype=jnp.float32):
    """Standard smoke batch for any arch config."""
    batch = {}
    if cfg.vocab_size > 1:
        batch["tokens"] = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
        batch["targets"] = jax.random.randint(
            jax.random.fold_in(key, 7), (B, S), 0, cfg.vocab_size
        )
    if cfg.frontend != "none" and cfg.n_prefix_embeds:
        batch["prefix_embeds"] = jax.random.normal(
            key, (B, cfg.n_prefix_embeds, cfg.d_model), dtype
        )
    if cfg.enc_dec:
        batch["enc_embeds"] = jax.random.normal(
            key, (B, cfg.enc_seq, cfg.d_model), dtype
        )
    if cfg.n_classes:
        batch["label"] = jax.random.randint(key, (B,), 0, cfg.n_classes)
    return batch
