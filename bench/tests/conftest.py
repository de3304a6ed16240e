"""Every test that takes `cell_name` runs once for each cell of
`BENCHMARK.json`."""
import pytest

from .tiny import CELLS


@pytest.fixture(params=CELLS)
def cell_name(request):
    return request.param
