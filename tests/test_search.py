"""Family-search invariants: feasibility, non-domination, determinism, and
the single-candidate degeneration to the stock planner."""
import pytest

from repro.configs import get_config
from repro.core.hardware import get_hardware
from repro.core.plan import derive_serve_plan, serve_feasible
from repro.core.search import (
    DesignPoint,
    SearchSpace,
    dominates,
    expected_accepted,
    family_report,
    pareto_frontier,
    search_family,
)


@pytest.fixture(scope="module", params=["tpu_v5e", "vck5000"])
def family(request):
    return search_family("qwen3-1.7b", get_hardware(request.param))


# ---------------------------------------------------------------- frontier
def test_every_frontier_point_is_feasible(family):
    ok, reason = serve_feasible(get_config("qwen3-1.7b"))
    assert ok, reason
    assert family.frontier
    for p in family.frontier:
        assert p.feasible, p.reason
        assert p.tokens_per_s > 0
        assert p.step_s > 0


def test_no_dominated_point_on_frontier(family):
    for p in family.frontier:
        assert not any(
            dominates(q, p) for q in family.frontier if q is not p
        )


def test_frontier_meets_acceptance_floor():
    # the --family acceptance bar: >= 3 non-dominated points on tpu_v5e
    v5e_family = search_family("qwen3-1.7b", get_hardware("tpu_v5e"))
    assert len(v5e_family.frontier) >= 3


def test_search_is_deterministic(family):
    again = search_family("qwen3-1.7b", get_hardware(family.hardware))
    assert [p.to_record() for p in family.points] == [
        p.to_record() for p in again.points
    ]
    assert [p.to_record() for p in family.frontier] == [
        p.to_record() for p in again.frontier
    ]


def test_vck5000_search_nonempty_and_single_chip():
    result = search_family("qwen3-1.7b", get_hardware("vck5000"))
    assert result.frontier
    # no interconnect => the mesh axis never leaves model=1
    assert all(p.mesh["model"] == 1 for p in result.points)


# ------------------------------------------------------------- degeneration
def test_single_candidate_space_degenerates_to_planner():
    """A space of all-None singletons must reproduce exactly the plan
    ``derive_serve_plan`` derives today — search adds options, never drift."""
    cfg = get_config("qwen3-1.7b")
    hw = get_hardware("tpu_v5e")
    space = SearchSpace(spec_lens=(None,))
    result = search_family(cfg, hw, space)
    assert len(result.points) == 1
    stock = derive_serve_plan(
        cfg, {"data": 1, "model": 1}, hw, max_seq_len=space.max_seq_len,
        draft=space.draft,
    )
    assert result.points[0].plan == stock
    assert result.frontier[0].plan == stock


# ------------------------------------------------------------------- units
def _pt(tok, usd, mj):
    return DesignPoint(
        hardware="h", arch="a", mesh={"data": 1, "model": 1}, plan=None,
        tile="", tokens_per_s=tok, usd_per_mtok=usd, mj_per_tok=mj,
        step_s=1.0, tokens_per_step=1.0, bound="memory", feasible=True,
    )


def test_dominates_semantics():
    a, b = _pt(10, 1.0, 1.0), _pt(5, 2.0, 2.0)
    assert dominates(a, b) and not dominates(b, a)
    # equal on all axes: neither dominates
    c = _pt(10, 1.0, 1.0)
    assert not dominates(a, c) and not dominates(c, a)
    # trade: faster but pricier — incomparable
    d = _pt(20, 3.0, 1.0)
    assert not dominates(a, d) and not dominates(d, a)


def test_pareto_frontier_filters_and_dedupes():
    pts = [_pt(5, 2.0, 2.0), _pt(10, 1.0, 1.0), _pt(10, 1.0, 1.0),
           _pt(20, 3.0, 1.0)]
    pts.append(_pt(1, 9.0, 9.0))
    pts[-1].feasible = False  # infeasible points never reach the frontier
    f = pareto_frontier(pts)
    assert [p.tokens_per_s for p in f] == [20, 10]  # sorted desc, deduped


def test_expected_accepted():
    assert expected_accepted(0, 0.6) == 1.0
    assert expected_accepted(4, 1.0) == 5.0
    # geometric series: 1 + a + ... + a^gamma
    assert expected_accepted(2, 0.5) == pytest.approx(1.75)


# ------------------------------------------------------------------ report
def test_family_report_record_and_markdown(tmp_path):
    result, record = family_report(
        "qwen3-1.7b", "tpu_v5e", out_dir=tmp_path
    )
    assert record["n_feasible"] >= len(record["frontier"]) >= 3
    md = record["markdown"]
    assert "| tok/s | $/Mtok | mJ/tok |" in md
    assert (tmp_path / "tpu_v5e__qwen3-1.7b.json").exists()
    # every frontier record carries a runnable plan + resolved tile
    for rec in record["frontier"]:
        assert rec["plan"]["decode_batch"] >= 1
        assert rec["tile"]
