"""The expert layer of the cells whose dispatch is bounded by the live
count in its forward too (``moe_rows.gather_pays``), forward and backward
under remat, compiled at real widths for a described v5e in its parent's
memory (the other cells: ``test_chip_compile_expert_rows.py``; the cells'
table and what each is held to: ``tests/chip_compile.py``)."""

import pytest

from tests.chip_compile import (  # noqa: F401  (fixtures by import)
    BOUNDED, expert_rows_compile_in_the_parents_memory, kernels_are_the_path,
    one_chip, topo)


@pytest.mark.parametrize("cell", sorted(BOUNDED))
def test_expert_rows_fwd_bwd_compile_in_the_parents_memory(
        one_chip, kernels_are_the_path, cell):
    expert_rows_compile_in_the_parents_memory(one_chip, cell)
