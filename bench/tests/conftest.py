"""A copy of the benchmark with the planned cells added (``planned.json``:
``kron20.f32.backlog``, planned for a later PR, and the test-only
``kron20.q25.mesh4``), so that the tests exercise the float32 comparison and
a cell on a four-device mesh through a whole rehearsal, as such cells will.
Their limits here are for tiny CPU graphs only; a cell's own are read on
the chip."""
from pathlib import Path

import pytest

from bench.tests.cells import CELLS, REPO, add_planned_cells


@pytest.fixture(scope="session")
def planned_root(tmp_path_factory) -> Path:
    return add_planned_cells(tmp_path_factory.mktemp("planned"))


@pytest.fixture(scope="session")
def root_of(planned_root):
    """The benchmark root that holds a cell: the repo, or the copy with the
    planned cells."""
    return lambda workload: planned_root if CELLS[workload]["planned"] \
        else REPO
