"""A run with the timed path broken underneath comes out not correct, once
for each fault the cells can have.  The faults are planted in the
single-device engine, so every one-chip cell that it serves is tried (no
exchange between chips to leave out).  The harness runs as on the chip,
less its look for one, at the generator's tiny size, against the cells' own
limits."""
import pytest

from bench.harness import rehearse
from bench.tests.cells import REPO, cells_where


def step_returns_state(monkeypatch):
    import repro.ppr_serving.engine.single as single

    monkeypatch.setattr(single, "make_ppr_fixed_step",
                        lambda fmt, n, alpha: lambda x, y, v, d, V, P: P)
    monkeypatch.setattr(single, "ppr_step_float",
                        lambda x, y, v, d, V, P, **kw: P)


def half_the_batch_left_out(monkeypatch):
    """The wave's second half of queries (rounded up) gets no
    personalization column: the service pads a partial wave with copies of
    its first query, so the queries are the columns before those copies."""
    import numpy as np

    import repro.ppr_serving.engine.single as single

    def half(make):
        def build(num_vertices, pers, *args):
            V = make(num_vertices, pers, *args)
            p = np.asarray(pers)
            m = len(p)
            while m > 1 and p[m - 1] == p[0]:
                m -= 1
            return V.at[:, m // 2:m].set(0)
        return build

    monkeypatch.setattr(single, "personalization_matrix_fixed",
                        half(single.personalization_matrix_fixed))
    monkeypatch.setattr(single, "personalization_matrix",
                        half(single.personalization_matrix))


def answer_altered(monkeypatch):
    import repro.ppr_serving.engine.base as base

    topk = base.topk_dense

    def altered(P, k, exclude=None):
        idx, vals = topk(P, k, exclude=exclude)
        return idx.at[:, 0].set((idx[:, 0] + 1) % P.shape[0]), vals

    monkeypatch.setattr(base, "topk_dense", altered)


@pytest.mark.parametrize("fault", [step_returns_state,
                                   half_the_batch_left_out, answer_altered])
@pytest.mark.parametrize("workload", cells_where(chips=1, engine="single"))
def test_fault_makes_the_run_not_correct(monkeypatch, root_of, fault,
                                         workload):
    fault(monkeypatch)
    r = rehearse(root_of(workload), workload, 2**31 + 21, 1.0)
    assert not r["correct"], r["checks"]


def test_the_same_run_unbroken_is_correct():
    r = rehearse(REPO, "kron20.q25.backlog", 2**31 + 21, 2.0)
    assert r["correct"], r["checks"]
