"""The control a correctness limit has to reject: the step down in precision
a later change could be tempted to take, read at a test's size on three
seeds.  A fixed-point cell compares exactly, so the next narrower paper
format (Q1.25 -> Q1.23) fails every answer; a float32 cell's numbers read
far higher with bfloat16 in the program's place (the readings at the cells'
own size, which set the limits, were taken on the chip and are in PERF.md).
"""
import pytest

from bench.harness import rehearse
from bench.readings import NARROWER, bf16_control
from bench.tests.cells import CELLS, cells_where

KRON = {"scale": 10}
SEEDS = (2**31 + 1, 2**31 + 2, 2**31 + 3)


def _runs(root, workload, precision=None, graph=None):
    return [rehearse(root, workload, s, 1.0, graph, precision=precision)
            for s in SEEDS]


@pytest.mark.parametrize("workload", [
    w for w in cells_where(chips=1) if CELLS[w]["precision"] in NARROWER])
def test_the_next_narrower_format_fails_the_exact_comparison(root_of,
                                                             workload):
    root = root_of(workload)
    assert all(r["correct"] for r in _runs(root, workload))
    for r in _runs(root, workload, NARROWER[CELLS[workload]["precision"]]):
        assert not r["correct"]
        assert r["checks"]["mismatched"]["value"] > 0


def test_bfloat16_in_the_programs_place_reads_far_higher(planned_root):
    program = _runs(planned_root, "kron20.f32.backlog", graph=KRON)
    control = [bf16_control("kron20.f32.backlog", s, 1.0, root=planned_root,
                            graph=KRON) for s in SEEDS]
    for name in ("err_max", "err_mean"):
        lower = max(r["checks"][name]["value"] for r in program)
        upper = min(c[name] for c in control)
        assert upper >= 30 * lower, (name, lower, upper)
