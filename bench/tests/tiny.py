"""Cells of the benchmark cut to a size a CPU test runs in a second."""
from harness.cell import Cell
from harness.files import ROOT

TINY_MATRIX = {
    "stencil27": {"nx": 10, "ny": 9, "nz": 8},
    "kronecker": {"scale": 9},
}


def tiny_cell(name: str, root: str = ROOT) -> Cell:
    cell = Cell(name, root=root)
    cell.config["matrix"].update(TINY_MATRIX[cell.config["matrix"]["generator"]])
    return cell
