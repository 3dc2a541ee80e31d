"""A whole run of a small cell on the CPU, past the look for a chip: the
window, the reference comparison and the result line."""
import benchpath  # noqa: F401  (first: puts bench/ on the path)
import pytest

import tinybench


@pytest.fixture(scope="module")
def reg(tmp_path_factory):
    return tinybench.make(tmp_path_factory.mktemp("bench"))


def test_open_loop_run_is_correct_and_reports_its_metrics(reg):
    out = tinybench.run(reg, "tiny.code")
    assert out["correct"], out["check"]
    assert set(out["metrics"]) == {"ttft_p85_ms", "itl_p95_ms", "setup_s"}
    assert out["attempted"] == round(6.0 * 2.0) and out["failed"] == 0
    assert list(out)[-1] == "check"
    assert out["check"]["compiles_in_window"]["value"] == 0
    assert out["check"]["tokens_missing"]["value"] == 0


def test_closed_loop_run_is_correct(reg):
    out = tinybench.run(reg, "tiny.batch")
    assert out["correct"], out["check"]
    assert set(out["metrics"]) == {"output_tok_s", "setup_s"}
    assert out["metrics"]["output_tok_s"]["value"] > 0
