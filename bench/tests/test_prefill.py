"""The ``prefill`` mode on a tiny looped configuration on the CPU:
``correct`` comes out true on a sound run and false on the control and
on each fault a looped prefill can have; the work counts against hand
counts; the per-op byte count and the roofline readers on small inputs.

The runs drive ``run.run_cell`` (the harness's look for a chip skipped)
with the limit of ``ouro2.6b.prefill_s4096``.
"""
from __future__ import annotations

import dataclasses
import json
import os
import time

import jax
import jax.numpy as jnp
import pytest

import prefill_reference
import prefill_work
import reference
import run

CELL = "ouro2.6b.prefill_s4096"
SEEDS = (5, 2 ** 31 + 17)
TINY = {"name": "ouro_tiny", "builder": "looplm", "arch": "ouro2.6b.py",
        "head_dim": 16, "hidden_size": 64, "intermediate_size": 96,
        "num_attention_heads": 4, "num_key_value_heads": 4,
        "num_hidden_layers": 2, "total_ut_steps": 3, "vocab_size": 128,
        "rms_norm_eps": 1e-6, "rope_theta": 1000000}
BATCH, SEQ = 2, 16


def _tiny_macs():
    tokens = BATCH * SEQ
    per_token = 64 * 192 + 64 * 64 + 64 * 192 + 96 * 64
    fc = per_token * tokens * 2 * 3
    attn = 2 * BATCH * 4 * 16 * (SEQ * (SEQ + 1) // 2) * 2 * 3
    head = BATCH * 64 * 128
    return {"batch": BATCH, "seq": SEQ, "total": fc + attn + head}


@pytest.fixture()
def tiny_cell():
    from repro.lower import fuse
    spec = run.cell_spec(CELL)
    fuse.clear_cache()
    yield {
        "name": "ouro_tiny.prefill", "chips": 1,
        "config": {**TINY, "macs": _tiny_macs()}, "arch": spec["arch"],
        "traffic": {"mode": "prefill", "batch": BATCH, "seq": SEQ,
                    "template": "eyeriss_multinode", "ring": 4},
        "checks": spec["checks"],
        "end_to_end": spec["end_to_end"], "per_layer": [],
    }
    fuse.clear_cache()


def _run(cell, seed=SEEDS[0]):
    return run.run_cell(cell, seed, 0.2, False, jax.devices(),
                        time.perf_counter(), {})


def _bad(line):
    err = line["checks"]["max_rel_err"]
    return line["correct"] is False and err["value"] > err["limit"]


@pytest.mark.parametrize("seed", SEEDS)
def test_sound_run_is_correct(tiny_cell, seed):
    line = _run(tiny_cell, seed)
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] >= 4
    assert set(line["metrics"]) == {"images_per_s", "setup_s"}
    assert list(line)[-1] == "checks"


def test_high_control_is_not_correct(tiny_cell, monkeypatch):
    """Every product of the program in three bf16 passes (what
    ``Precision.HIGH`` does on a TPU), written out as the explicit split
    so that it computes the same on the CPU."""
    from repro.lower import fuse
    attention = fuse._attention
    monkeypatch.setattr(fuse, "_fc", lambda plan, x, w: reference._product(
        jnp.dot, x, w, "high"))

    def high_attention(plan, q, k, v):
        einsum = jnp.einsum

        def split(spec, a, b, **kw):
            return reference._product(lambda x, y: einsum(spec, x, y), a, b,
                                      "high")
        monkeypatch.setattr(jnp, "einsum", split)
        try:
            return attention(plan, q, k, v)
        finally:
            monkeypatch.setattr(jnp, "einsum", einsum)
    monkeypatch.setattr(fuse, "_attention", high_attention)
    assert _bad(_run(tiny_cell))


def _without_meta(monkeypatch, key):
    from repro.lower import fuse
    attention = fuse._attention

    def dropped(plan, q, k, v):
        meta = {m: v_ for m, v_ in plan.layer.meta.items() if m != key}
        layer = dataclasses.replace(plan.layer, meta=meta)
        return attention(dataclasses.replace(plan, layer=layer), q, k, v)
    monkeypatch.setattr(fuse, "_attention", dropped)


def test_dropped_causal_mask_is_not_correct(tiny_cell, monkeypatch):
    _without_meta(monkeypatch, "causal")
    assert _bad(_run(tiny_cell))


def test_dropped_rope_is_not_correct(tiny_cell, monkeypatch):
    _without_meta(monkeypatch, "rope_theta")
    assert _bad(_run(tiny_cell))


def test_untied_weights_are_not_correct(tiny_cell, monkeypatch):
    """Each loop step reads a draw of its own, where the model shares
    the first step's weights."""
    from repro.workloads.layers import LayerSpec
    prefill = run.load_module(os.path.join(run.BENCH, "modes",
                                           "prefill.py"))
    make_arrays = prefill.make_arrays

    def own_draws(cfg, batch, seq, seed, ring):
        weights, slots = make_arrays(cfg, batch, seq, seed, ring)
        key = jax.random.PRNGKey(seed)
        for name, w in list(weights.items()):
            for t in range(1, cfg["total_ut_steps"]):
                if name.startswith("s0."):
                    key, sub = jax.random.split(key)
                    weights[f"s{t}." + name[3:]] = w[
                        jax.random.permutation(sub, w.shape[0])]
        return weights, slots
    monkeypatch.setattr(prefill, "make_arrays", own_draws)
    monkeypatch.setattr(run, "load_module", lambda path: prefill)
    monkeypatch.setattr(LayerSpec, "weight_owner",
                        property(lambda self: self.name))
    assert _bad(_run(tiny_cell))


def test_dropped_post_norm_is_not_correct(tiny_cell, monkeypatch):
    from repro.lower import fuse
    norm = fuse._norm
    monkeypatch.setattr(fuse, "_norm", lambda plan, x, g: x
                        if plan.layer.name.endswith("post_norm")
                        else norm(plan, x, g))
    assert _bad(_run(tiny_cell))


# ---------------------------------------------------------------------------
# work counts
# ---------------------------------------------------------------------------

def test_work_counts_match_hand_counts():
    spec = run.cell_spec(CELL)
    cfg = spec["config"]
    layers = spec["arch"].layers(cfg, 1, 4096)
    # per token, one application: qkv 2048x6144, o 2048x2048, gate_up
    # 2048x11264, down 5632x2048
    per_token = 12582912 + 4194304 + 23068672 + 11534336
    assert per_token == 51380224
    fc = per_token * 4096 * 48
    attn = 16 * 128 * (4096 * 4097 // 2) * 2 * 48
    assert fc == 10101763080192 and attn == 1649670094848
    assert prefill_work.total_macs(layers) == fc + attn + 2048 * 49152 \
        == cfg["macs"]["total"]
    a = next(l for l in layers if l["kind"] == "attention")
    assert prefill_work.flops(a) == 2 * 34368126976
    # q, k and v read once, the output written once, in float32
    assert prefill_work.min_bytes(a) == 4 * (4096 * 6144 + 4096 * 2048)
    head = layers[-1]
    assert prefill_work.min_bytes(head) == 4 * (2048 + 2048 * 49152
                                                + 49152)


def test_tiny_config_macs_match_the_builder(tiny_cell):
    prefill = run.load_module(os.path.join(run.BENCH, "modes",
                                           "prefill.py"))
    prep = prefill.prepare(tiny_cell)
    assert prep["macs"] == _tiny_macs()["total"]
    assert len(prep["layers"]) == 1 + 3 * (2 * 12 + 1) + 1


HLO = """\
HloModule jit_fn

%fused_norm (param_0: f32[8,4]) -> f32[8] {
  %param_0 = f32[8,4]{1,0} parameter(0)
  %mul = f32[8,4]{1,0} multiply(%param_0, %param_0)
  ROOT %r = f32[8]{0} reduce(%mul, %c), dimensions={1}, to_apply=%add
}

%fused_last (param_0.1: f32[8,4], param_1: f32[4]) -> f32[1,4] {
  %param_0.1 = f32[8,4]{1,0} parameter(0)
  %s = f32[1,4]{1,0} slice(%param_0.1), slice={[7:8], [0:4]}
  %param_1 = f32[4]{0} parameter(1)
  %b = f32[1,4]{1,0} broadcast(%param_1), dimensions={1}
  ROOT %m = f32[1,4]{1,0} multiply(%s, %b)
}

ENTRY %main (x: f32[8,4], g: f32[4]) -> f32[1,4] {
  %x = f32[8,4]{1,0} parameter(0)
  %g = f32[4]{0} parameter(1)
  %fusion.1 = f32[8]{0} fusion(%x), kind=kLoop, calls=%fused_norm
  %gte = f32[8]{0} get-tuple-element(%fusion.1), index=0
  ROOT %fusion.2 = f32[1,4]{1,0} fusion(%x, %g), kind=kLoop, calls=%fused_last
}
"""


def test_op_bytes_counts_outputs_operands_and_slices():
    got = prefill_work.op_bytes(HLO)
    assert got == {"fusion.1": 4 * (8 + 32), "fusion.2": 4 * (4 + 4 + 4)}


def test_roofline_readers_on_a_small_context():
    peaks = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    attn = run.load_module(os.path.join(run.BENCH, "metrics",
                                        "attn_roofline.prefill.py"))
    normglu = run.load_module(os.path.join(run.BENCH, "metrics",
                                           "normglu_roofline.prefill.py"))
    ctx = {"peaks": peaks, "forwards": 2,
           "trace": {"layers": {"a0": 8.0, "n0": 1.0}},
           "layer_work": {"a0": {"kind": "attention", "flops": 200,
                                 "min_bytes": 10},
                          "a1": {"kind": "attention", "flops": 900,
                                 "min_bytes": 10}},
           "kinds": {"a0": "attention", "a1": "attention", "n0": "norm",
                     "g0": "glu"},
           "op_layers": {"r.1": "n0", "q.2": "g0", "d.3": "a0"},
           "op_bytes": {"r.1": 30, "q.2": 5, "d.3": 99},
           "op_seconds": {"r.1": 10.0, "d.3": 8.0}}
    # a1 owns no device time: left out; 2 forwards x 2 s least / 8 s
    assert attn.read(ctx) == pytest.approx(50.0)
    # g0's op never ran; 2 forwards x 30 B / 10 B/s / 10 s
    assert normglu.read(ctx) == pytest.approx(60.0)
    assert attn.read({**ctx, "trace": None}) is None
    assert normglu.read({**ctx, "op_seconds": {}}) is None


def test_config_holds_the_catalog_entry():
    cfg = run.cell_spec(CELL)["config"]
    bm = run.load_json(os.path.join(run.ROOT, "BENCHMARK.json"))
    conf = {c["name"]: c for c in bm["configs"]}["ouro2.6b"]
    assert conf["reduced"] == ["num_hidden_layers"]
    assert cfg["num_hidden_layers"] == 12
    assert cfg["published_num_hidden_layers"] == 48
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["head_dim"], cfg["intermediate_size"], cfg["vocab_size"],
            cfg["total_ut_steps"]) == (2048, 16, 128, 5632, 49152, 4)
    assert len(cfg["layer_types"]) == 48
    assert json.loads(json.dumps(cfg)) == cfg


def test_reference_loop_reuses_one_weight_dict():
    """The reference's feed names the first step's weights only."""
    spec = prefill_reference.feeds(TINY, BATCH, SEQ)
    names = [k for k in spec if k.endswith(".W")]
    assert all(k.startswith("s0.") or k == "head.W" for k in names)
    assert len(names) == 2 * 8 + 2
