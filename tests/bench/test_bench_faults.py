"""The comparison that decides ``correct`` fails when the timed path is
broken underneath: a token altered where it is produced, and a step that
returns the KV pools unchanged.  (Half a batch left out and the exchange
between chips do not apply: each slot's tokens are checked one by one, and
the cells run on one chip.)"""
import benchpath  # noqa: F401  (first: puts bench/ on the path)
import jax
import jax.numpy as jnp
import pytest

import tinybench


@pytest.fixture(scope="module")
def reg(tmp_path_factory):
    return tinybench.make(tmp_path_factory.mktemp("bench"))


def altered_token(engine):
    step = engine._step

    def broken(params, pools, tokens, tables, lens, kinds, poison):
        tok, vtok, finite, pools = step(params, pools, tokens, tables, lens, kinds, poison)
        tok = (tok + 1) % engine.cfg.vocab_size
        return tok, tok[:, None], finite, pools

    engine._step = broken


def state_unchanged(engine):
    step = engine._step

    def broken(params, pools, tokens, tables, lens, kinds, poison):
        copy = jax.tree.map(jnp.copy, pools)
        tok, vtok, finite, _ = step(params, copy, tokens, tables, lens, kinds, poison)
        return tok, vtok, finite, pools

    engine._step = broken


@pytest.mark.parametrize("fault", [altered_token, state_unchanged])
@pytest.mark.parametrize("workload", ["tiny.code", "tiny.batch"])
def test_fault_makes_the_run_incorrect(reg, fault, workload):
    out = tinybench.run(reg, workload, fault=fault)
    assert out["correct"] is False
    assert out["check"]["max_logit_gap"]["value"] > out["check"]["max_logit_gap"]["limit"]
