"""Work counts: each configuration's layer list is the program's graph,
and counts the conv+fc MACs its file states."""
from __future__ import annotations

import os

import pytest

import run
import work

CELLS = [w["name"] for w in run.load_json(
    os.path.join(run.ROOT, "BENCHMARK.json"))["workloads"]]


@pytest.mark.parametrize("cell_name", CELLS)
def test_config_layers_match_the_program(cell_name):
    from repro.workloads.nets import get_net
    infer = run.load_module(os.path.join(run.BENCH, "modes", "infer.py"))
    cell = run.cell_spec(cell_name)
    batch = cell["traffic"]["batch"]
    layers = cell["arch"].layers(cell["config"], batch)
    graph = get_net(cell["config"]["builder"], batch=batch)
    assert infer.check_graph(graph, layers, cell["config"], batch) \
        == work.total_macs(layers)


def test_counts_of_one_conv():
    conv = {"kind": "conv", "N": 2, "C": 3, "K": 4, "X": 5, "Y": 5,
            "R": 3, "S": 3, "stride": 1}
    assert work.macs(conv) == 2 * 3 * 4 * 5 * 5 * 9
    assert work.flops(conv) == 2 * work.macs(conv)
    assert work.min_bytes(conv) == 4 * (2 * 3 * 7 * 7 + 4 * 3 * 9
                                        + 2 * 4 * 5 * 5)
    pool = {"kind": "pool", "N": 1, "C": 2, "X": 2, "Y": 2, "R": 2,
            "S": 2, "stride": 2}
    assert work.macs(pool) == 0 and work.flops(pool) == 32
    add = {"kind": "eltwise", "N": 1, "C": 2, "X": 2, "Y": 2, "srcs": 2}
    assert work.flops(add) == 8 and work.min_bytes(add, 2) == 2 * 8 * 3
