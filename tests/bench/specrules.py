"""Rules that every configuration and cell of a benchmark spec keeps, as
functions of its registry, so that the repo's spec and a copy grown by a
later configuration are held to the same ones.

A cell's control readings live beside the tests of the checkout the
registry's ``bench/`` sits in: ``tests/bench/data/control/<cell>.json``, a
list of the chip runs of its control (the reference in the precision below
the one its configuration states), each with the program's own gaps on the
same sample."""
from __future__ import annotations

import pathlib

from bench import harness, registry


def control_file(reg, cell: str) -> pathlib.Path:
    return reg.base.parent / "tests" / "bench" / "data" / "control" / f"{cell}.json"


def served_by_its_module(reg, name: str) -> None:
    """Configuration ``name`` is served by ``archs/<its arch_module>.py``,
    ``archs/dense.py`` where its file names none."""
    c = reg.config(name)
    want = reg.base / "archs" / f"{c.get('arch_module', 'dense')}.py"
    assert pathlib.Path(reg.arch(c).__file__) == want, name


def limits_fail_the_control(reg, cell: str) -> None:
    """Under the cell's committed limits (``checks/<cell>.json``) each of
    its chip control runs, three or more, reads ``correct`` false, and the
    program's own gaps on the same samples read true."""
    readings = registry.load_json(control_file(reg, cell))
    check = registry.load_json(reg.base / "checks" / f"{cell}.json")
    assert len(readings) >= 3, cell
    for r in readings:
        assert harness.decide(r["control"], check, 0)[0] is False, r
        assert harness.decide(r["program"], check, 0)[0] is True, r
