"""Cells for the CPU tests, at tiny sizes (the configurations' own sizes
need a chip). ``terasort_4chip_cpu`` is the TeraSort cell on four
virtual devices: the harness's multi-chip path (sampling across chips,
the all-to-all, the reference over chips) with no four-chip cell in
BENCHMARK.json yet."""

import dataclasses
import os

from perfbench import registry

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
FOUR_CHIP = "terasort_4chip_cpu"
#: tiny records per chip for each cell
TINY = {"terasort_100b_1chip": 4096, "repartition256_1chip": 65536,
        FOUR_CHIP: 2048}


def make_cell(name: str) -> registry.Cell:
    if name == FOUR_CHIP:
        one = registry.load_cell(ROOT, "terasort_100b_1chip")
        return dataclasses.replace(one, name=FOUR_CHIP, chips=4)
    return registry.load_cell(ROOT, name)
