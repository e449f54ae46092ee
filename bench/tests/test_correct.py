"""``correct`` comes out true on a sound run and false on the control and
on each fault a timed inference path can have.

The sound run and the faults drive ``run.run_cell`` (the harness's look
for a chip skipped) on a tiny net on the CPU, with the limit of
``resnet50.infer_b64``.  The control readings are taken on the edge
cell's own size, ResNet-50 at batch 1, against that cell's limit.
"""
from __future__ import annotations

import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference
import run
import tiny_arch

SEEDS = (7, 2 ** 31 + 11, 4_000_000_123)


@pytest.fixture()
def tiny_cell(monkeypatch):
    from repro.lower import fuse
    from repro.workloads import nets
    monkeypatch.setitem(nets.NETS, "bench_tiny",
                        lambda batch=4: tiny_arch.program_graph(batch))
    fuse.clear_cache()
    yield {
        "name": "tiny.infer_b4", "chips": 1, "config": tiny_arch.CONFIG,
        "arch": tiny_arch,
        "traffic": {"mode": "infer", "batch": 4,
                    "template": "eyeriss_multinode", "ring": 4},
        "checks": run.load_json(os.path.join(
            run.BENCH, "checks", "resnet50.infer_b64.json")),
        "end_to_end": [{"name": "images_per_s", "unit": "images/s"},
                       {"name": "setup_s", "unit": "s"}],
        "per_layer": [],
    }
    fuse.clear_cache()


def _run(cell, seed=SEEDS[0]):
    return run.run_cell(cell, seed, 0.3, False, jax.devices(),
                        time.perf_counter(), {})


def test_sound_run_is_correct(tiny_cell):
    line = _run(tiny_cell)
    assert line["correct"] is True
    assert line["attempted"] >= 4
    assert set(line["metrics"]) == {"images_per_s", "setup_s"}
    assert list(line)[-1] == "checks"
    err = line["checks"]["max_rel_err"]
    assert 0 <= err["value"] <= err["limit"]


def test_half_batch_left_out_is_not_correct(tiny_cell, monkeypatch):
    from repro.lower import fuse
    conv = fuse._conv

    def half(plan, x, w):
        out = conv(plan, x, w)
        return out.at[out.shape[0] // 2:].set(0.0)
    monkeypatch.setattr(fuse, "_conv", half)
    assert _run(tiny_cell)["correct"] is False


def test_answer_altered_where_produced_is_not_correct(tiny_cell,
                                                     monkeypatch):
    from repro.lower import fuse
    fc = fuse._fc

    def altered(plan, x, w):
        out = fc(plan, x, w)
        return out.at[1, 3].multiply(1.001)
    monkeypatch.setattr(fuse, "_fc", altered)
    line = _run(tiny_cell)
    assert line["correct"] is False
    assert line["checks"]["max_rel_err"]["value"] > \
        line["checks"]["max_rel_err"]["limit"]


def test_non_finite_output_is_not_correct(tiny_cell, monkeypatch):
    from repro.lower import fuse
    fc = fuse._fc
    monkeypatch.setattr(fuse, "_fc", lambda plan, x, w:
                        fc(plan, x, w).at[0, 0].set(jnp.nan))
    assert _run(tiny_cell)["correct"] is False


@pytest.mark.parametrize("seed", SEEDS)
def test_control_fails_the_edge_limit(seed):
    """The reference in three bf16 passes, in the program's place, reads
    above ``resnet50.edge_b1``'s limit at that cell's size."""
    cell = run.cell_spec("resnet50.edge_b1")
    infer = run.load_module(os.path.join(run.BENCH, "modes", "infer.py"))
    layers = cell["arch"].layers(cell["config"], 1)
    weights, slots = infer.make_arrays(layers, seed, 1)
    arrays = {**weights, **slots[0]}
    names = [l["name"] for l in layers]
    ctrl = reference.forward(layers, arrays, names, "high")
    errs = reference.compare(layers, arrays, ctrl)
    limit = cell["checks"]["max_rel_err"]["limit"]
    assert max(errs.values()) > limit
    exact = reference.forward(layers, arrays, names, "highest")
    assert max(reference.compare(layers, arrays, exact).values()) == 0.0


def test_seed_past_32_bits_draws_the_same_arrays():
    infer = run.load_module(os.path.join(run.BENCH, "modes", "infer.py"))
    layers = tiny_arch.layers({}, 4)
    a, _ = infer.make_arrays(layers, 2 ** 33 + 5, 2)
    b, _ = infer.make_arrays(layers, 2 ** 33 + 5, 2)
    c, _ = infer.make_arrays(layers, 2 ** 33 + 6, 2)
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert not np.array_equal(a["fc.W"], c["fc.W"])


def test_compiling_inside_the_window_fails_the_run(tiny_cell, monkeypatch):
    """A call that compiles a new program once set-up is over stops the
    run: nothing may compile inside the measured window."""
    infer = run.load_module(os.path.join(run.BENCH, "modes", "infer.py"))
    runners_for = infer.runners_for
    calls = [0]

    def recompiling(nplan, weights, slots):
        runners = runners_for(nplan, weights, slots)
        first = runners[0]

        def fresh():
            calls[0] += 1
            jax.jit(lambda x, k=calls[0]: x + k)(1.0).block_until_ready()
            return first()
        return [fresh] + runners[1:]
    monkeypatch.setattr(infer, "runners_for", recompiling)
    monkeypatch.setattr(run, "load_module", lambda path: infer)
    with pytest.raises(RuntimeError, match="compiled inside the window"):
        _run(tiny_cell)
