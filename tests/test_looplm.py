"""A looped transformer's prefill (``nets.looplm``) on the served path:
solved, lowered and run through ``lower_network`` + ``FusedNetwork`` and
the interpret per-layer runner, every layer held to the model-level
oracle ``kernels.ref.looplm_ref``; weights shared across loop steps; the
causal mask; the solver's resident-dim rule at full width; the layer
scopes of the new kinds."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.cost_model import evaluate_layer
from repro.core.solver import solve
from repro.core.solver.intralayer import Constraints, solve_intra_layer
from repro.hw.presets import PRESETS
from repro.kernels import ref
from repro.lower import lower_network, make_network_inputs, network_runner
from repro.lower.exec import ORACLE_TOL, rel_error
from repro.lower.fuse import (CAUSAL_MAX_BLOCKS, CAUSAL_QUERY_BLOCK,
                              FusedNetwork,
                              attention_pair_share, causal_blocks,
                              compiled_plan_fn, fused_runner, input_specs)
from repro.lower.plan import lower_scheme
from repro.obs import metrics
from repro.workloads.layers import LayerGraph, attention, fc, rmsnorm
from repro.workloads.nets import LOOPLM_PARTS, get_net

TINY = dict(seq=16, hidden=64, heads=4, kv_heads=4, head_dim=16, ffn=96,
            layers=2, steps=3, vocab=128)
BATCH = 2
PRESET_NAMES = ("eyeriss_multinode", "tpu_like_edge")


def _ref(inputs, sizes=TINY, batch=BATCH):
    weights = {k: v for k, v in inputs.items() if k.endswith(".W")}
    x = inputs["embed.I"].reshape(batch * sizes["seq"], sizes["hidden"])
    return ref.looplm_ref(
        x, weights, batch=batch, seq=sizes["seq"], heads=sizes["heads"],
        kv_heads=sizes["kv_heads"], head_dim=sizes["head_dim"],
        layers=sizes["layers"], steps=sizes["steps"], eps=1e-6,
        rope_theta=1e6)


def _worst(outputs, want):
    errs = {n: rel_error(outputs[n].reshape(want[n].shape), want[n])
            for n in want}
    name = max(errs, key=errs.get)
    return name, errs[name]


@pytest.fixture(scope="module", params=PRESET_NAMES)
def tiny(request):
    hw = PRESETS[request.param]()
    graph = get_net("looplm", batch=BATCH, **TINY)
    sched = solve(graph, hw)
    assert sched.valid
    nplan = lower_network(sched, graph, hw)
    assert nplan.executable, nplan.invalid_layers()
    return nplan, make_network_inputs(nplan, seed=3)


def test_graph_names_parts_steps_and_ties():
    graph = get_net("looplm", batch=BATCH, **TINY)
    names = [l.name for l in graph.layers]
    per_step = TINY["layers"] * len(LOOPLM_PARTS) + 1
    assert len(names) == 1 + TINY["steps"] * per_step + 1
    assert names[0] == "embed" and names[-1] == "head"
    assert names[1:1 + len(LOOPLM_PARTS)] == [f"s0.l0.{p}"
                                              for p in LOOPLM_PARTS]
    assert graph.by_name["s1.l0.in_norm"].src == ("s0.norm",)
    assert graph.by_name["s2.l1.qkv"].weight_owner == "s0.l1.qkv"
    assert graph.by_name["s2.norm"].weight_owner == "s0.norm"
    assert all(graph.by_name[n].weight_owner == n for n in names
               if n.startswith("s0.") or n in ("embed", "head"))
    attn = graph.by_name["s1.l1.attn"]
    assert attn.kind == "attention" and attn.meta["causal"] == 1
    assert attn.meta["rope_theta"] == 1e6
    assert graph.by_name["head"].meta["last_position"] == TINY["seq"]


def test_compiled_every_layer_matches_looplm_ref(tiny):
    nplan, inputs = tiny
    ex = network_runner(nplan, inputs, backend="compiled", keep="all")()
    want = _ref(inputs)
    assert set(want) == set(nplan.order)
    name, err = _worst(ex.outputs, want)
    assert err < ORACLE_TOL, f"{name}: {err:.2e}"
    # the served variant returns the logits alone, the same numbers
    out = network_runner(nplan, inputs, backend="compiled",
                         keep="outputs")().outputs
    assert list(out) == ["head"]
    assert out["head"].shape == (BATCH, TINY["vocab"])
    assert rel_error(out["head"], want["head"]) < ORACLE_TOL


def test_grouped_kv_heads_match_looplm_ref():
    """Two K/V heads shared by four query heads: ``qkv`` is narrower and
    each K/V head repeats over its query heads."""
    sizes = {**TINY, "kv_heads": 2, "steps": 2}
    hw = PRESETS["eyeriss_multinode"]()
    graph = get_net("looplm", batch=BATCH, **sizes)
    assert graph.by_name["s0.l0.qkv"].dim("K") == (4 + 2 * 2) * 16
    nplan = lower_network(solve(graph, hw), graph, hw)
    inputs = make_network_inputs(nplan, seed=5)
    ex = network_runner(nplan, inputs, backend="compiled", keep="all")()
    name, err = _worst(ex.outputs, _ref(inputs, sizes))
    assert err < ORACLE_TOL, f"{name}: {err:.2e}"


def test_interpret_every_layer_matches_looplm_ref(tiny):
    nplan, inputs = tiny
    ex = network_runner(nplan, inputs, backend="interpret")()
    name, err = _worst(ex.outputs, _ref(inputs))
    assert err < ORACLE_TOL, f"{name}: {err:.2e}"


def test_tied_weights_fed_once_and_read_by_every_step(tiny):
    nplan, inputs = tiny
    owners = {k[:-2] for k in inputs if k.endswith(".W")}
    assert owners == {f"s0.l{i}.{p}" for i in range(TINY["layers"])
                      for p in ("in_norm", "qkv", "o", "attn_post_norm",
                                "ffn_norm", "gate_up", "down",
                                "ffn_post_norm")} | {"s0.norm", "head"}
    fused = fused_runner(nplan, cache=False)
    assert fused.weight_arrays == len(owners)
    assert fused.tied_layers == (TINY["steps"] - 1) * (
        TINY["layers"] * 8 + 1)
    snap = metrics.REGISTRY.snapshot()
    assert snap["fused_weight_arrays"]["series"][0]["value"] == len(owners)
    assert snap["fused_tied_layers"]["series"][0]["value"] \
        == fused.tied_layers

    base = fused(inputs, keep="all")
    moved = dict(inputs)
    moved["s0.l1.qkv.W"] = inputs["s0.l1.qkv.W"] * 1.5
    after = fused(moved, keep="all")
    for t in range(TINY["steps"]):
        name = f"s{t}.l1.qkv"
        assert rel_error(after[name], base[name]) > 1e-3, name
    # the first block reads no qkv of layer 1
    np.testing.assert_array_equal(after["s0.l0.add2"], base["s0.l0.add2"])


def test_causal_prefix_unchanged_by_a_later_token(tiny):
    nplan, inputs = tiny
    j, seq = 9, TINY["seq"]
    x = inputs["embed.I"]
    tokens = x.reshape(BATCH, seq, -1)
    moved = dict(inputs)
    moved["embed.I"] = tokens.at[:, j].add(1.0).reshape(x.shape)
    fused = fused_runner(nplan)
    base, after = fused(inputs, keep="all"), fused(moved, keep="all")
    for name in nplan.order:
        if name == "head":
            continue
        a = np.asarray(base[name]).reshape(BATCH, seq, -1)
        b = np.asarray(after[name]).reshape(BATCH, seq, -1)
        scale = np.abs(a).max()
        np.testing.assert_allclose(b[:, :j], a[:, :j], rtol=0,
                                   atol=1e-6 * scale, err_msg=name)
        assert np.abs(b[:, j:] - a[:, j:]).max() > 1e-4 * scale, name


def test_compiled_attention_plan_is_causal_with_rope():
    hw = PRESETS["eyeriss_multinode"]()
    layer = attention("t.attn", 2, 4, 64, 32, causal=True, rope_theta=1e4)
    scheme, cost = solve_intra_layer(layer, hw)
    plan = lower_scheme(scheme, hw)
    assert plan.valid, plan.reason
    fn, names = compiled_plan_fn(plan)
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (jax.random.normal(kk, (8, 64, 32), jnp.float32)
               for kk in keys)
    want = ref.attention_ref(ref.rope_ref(q[:, None], 1e4),
                             ref.rope_ref(k[:, None], 1e4), v[:, None],
                             causal=True)[:, 0]
    assert names == ("Q", "K", "V")
    assert rel_error(fn(q, k, v), want) < ORACLE_TOL
    # without the mask the first query would see every key
    full = ref.attention_ref(q[:, None], k[:, None], v[:, None],
                             causal=False)[:, 0]
    assert rel_error(fn(q, k, v), full) > 1e-2


def _full_mask(q, k, v, causal):
    """The single-einsum formulation over the whole score matrix, the
    mask applied to all of it."""
    sq, skv = q.shape[1], k.shape[1]
    s = jnp.einsum("nqd,nkd->nqk", q, k,
                   precision=jax.lax.Precision.HIGHEST) * q.shape[-1] ** -0.5
    if causal:
        keep = np.arange(skv)[None] <= np.arange(sq)[:, None] + (skv - sq)
        s = jnp.where(keep, s, -1e30)
    return jnp.einsum("nqk,nkd->nqd", jax.nn.softmax(s, axis=-1), v,
                      precision=jax.lax.Precision.HIGHEST)


BLOCKED_CASES = {
    "aligned": dict(sq=1024),
    "ragged": dict(sq=1100),
    "key_offset": dict(sq=1024, skv=1280),
    "grouped_kv": dict(sq=1024, heads=4, kv_heads=2),
    "rope": dict(sq=1024, rope_theta=1e4),
    "non_causal": dict(sq=1024, causal=False),
}


@pytest.mark.parametrize("case", list(BLOCKED_CASES))
def test_blocked_attention_matches_ref_and_full_mask(case):
    """A causal layer of two query blocks or more runs in the blocks of
    ``causal_blocks``; it matches the oracle and the whole-matrix
    formulation.  A non-causal layer takes the single-einsum path."""
    c = {"skv": None, "heads": 2, "kv_heads": None, "rope_theta": 0.0,
         "causal": True, **BLOCKED_CASES[case]}
    sq, skv, h, d = c["sq"], c["skv"] or c["sq"], c["heads"], 32
    kv = c["kv_heads"] or h
    hw = PRESETS["eyeriss_multinode"]()
    layer = attention("t.attn", 1, h, sq, d, seq_kv=c["skv"],
                      causal=c["causal"], rope_theta=c["rope_theta"],
                      kv_heads=c["kv_heads"])
    plan = lower_scheme(solve_intra_layer(layer, hw)[0], hw)
    assert plan.valid, plan.reason
    assert len(causal_blocks(sq, skv)) > 1    # long enough to split
    kq, kk, kvv = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(kq, (h, sq, d), jnp.float32)
    k, v = (jax.random.normal(key, (kv, skv, d), jnp.float32)
            for key in (kk, kvv))
    qr, kr = q, k                        # the layer rotates q and k itself
    if c["rope_theta"]:
        qr, kr = (ref.rope_ref(a[:, None], c["rope_theta"])[:, 0]
                  for a in (q, k))
    want = ref.attention_ref(qr[None], kr[None], v[None],
                             causal=c["causal"])[0]

    def rep(a):                          # as split_qkv repeats K/V heads
        return jnp.repeat(a, h // kv, axis=0)

    fn = compiled_plan_fn(plan)[0]
    text = jax.jit(fn).lower(q, rep(k), rep(v)).as_text(dialect="hlo")
    assert (f"f32[{h},{sq},{skv}]" in text) != c["causal"]
    if c["causal"]:                      # each block's scores, no more
        assert all(f"f32[{h},{q1 - q0},{kend}]" in text
                   for q0, q1, kend in causal_blocks(sq, skv))
    got = fn(q, rep(k), rep(v))
    assert rel_error(got, want) < ORACLE_TOL
    assert rel_error(got, _full_mask(qr, rep(kr), rep(v), c["causal"])) \
        < 1e-6


@pytest.mark.parametrize("sq,skv", [(16, 16), (1023, 1023), (1024, 1024),
                                    (1100, 1100), (1024, 1280),
                                    (2100, 2100), (4096, 4096),
                                    (8192, 8192), (1536, 1024)])
def test_causal_blocks_cover_each_row_once_within_its_keys(sq, skv):
    blocks = causal_blocks(sq, skv)
    rows = [i for q0, q1, _ in blocks for i in range(q0, q1)]
    assert rows == list(range(sq))
    for q0, q1, kend in blocks:
        # the block's last row sees every key below kend; no row of the
        # block sees a key past it
        assert kend == min(skv, q1 + skv - sq)
    if sq < 2 * CAUSAL_QUERY_BLOCK or skv < sq:
        assert blocks == [(0, sq, skv)]
        return
    sizes = {q1 - q0 for q0, q1, _ in blocks[:-1]}
    assert len(sizes) == 1 and blocks[-1][1] - blocks[-1][0] <= min(sizes)
    assert min(sizes) % 128 == 0 and min(sizes) >= CAUSAL_QUERY_BLOCK
    assert 2 <= len(blocks) <= CAUSAL_MAX_BLOCKS


def _s4096_probe(causal):
    hw = PRESETS["eyeriss_multinode"]()
    graph = LayerGraph("probe", [
        fc("qkv", 4096, 2048, 6144),
        attention("attn", 1, 16, 4096, 128, src=["qkv"], causal=causal,
                  rope_theta=1e6)])
    nplan = lower_network(solve(graph, hw), graph, hw)
    assert nplan.executable, nplan.invalid_layers()
    return FusedNetwork(nplan)


def _pair_gauge():
    snap = metrics.REGISTRY.snapshot()
    return snap["fused_attention_pair_share"]["series"][0]["value"]


@pytest.mark.parametrize("causal", [True, False])
def test_s4096_attention_forms_its_causal_prefix_alone(causal):
    """Ouro-2.6B's ``attention(1, 16, 4096, 128)``: causal, 4 query blocks
    of 1024 rows form 10 of the 16 block pairs and no [16, 4096, 4096]
    score matrix is traced; non-causal, the whole matrix.  Lowered
    only."""
    fused = _s4096_probe(causal)
    share = 10 / 16 if causal else 1.0
    assert fused.attention_pair_share == pytest.approx(share)
    assert _pair_gauge() == pytest.approx(share)
    specs = input_specs(fused.nplan)
    acts = {k: v for k, v in specs.items() if not k.endswith(".W")}
    weights = {k: v for k, v in specs.items() if k.endswith(".W")}
    text = fused._fn(("net", "outputs", False)).lower(
        acts, weights).as_text(dialect="hlo")
    assert ("f32[16,4096,4096]" in text) != causal
    widths = {int(w) for w in re.findall(r"f32\[16,1024,(\d+)\]", text)
              if int(w) >= 1024}         # score blocks, not row sums or PV
    assert widths == ({1024, 2048, 3072, 4096} if causal else set())


def test_pair_share_reads_one_without_a_split_causal_layer(tiny):
    nplan, _ = tiny
    layers = [nplan.plans[n].layer for n in nplan.order]
    assert any(l.kind == "attention" and l.meta.get("causal")
               for l in layers)
    assert attention_pair_share(layers) == 1.0
    assert fused_runner(nplan, cache=False).attention_pair_share == 1.0
    assert _pair_gauge() == 1.0
    assert attention_pair_share([attention("a", 1, 16, 4096, 128)]) == 1.0


@pytest.mark.parametrize("preset", PRESET_NAMES)
def test_s4096_attention_and_norm_lower_valid(preset):
    """At Ouro-2.6B's widths the solver keeps the head dim and the norm's
    channel row below the DRAM level, so lowering accepts its schemes
    with no repair."""
    hw = PRESETS[preset]()
    graph = LayerGraph("probe", [
        rmsnorm("norm", 4096, 2048, 1e-6),
        fc("qkv", 4096, 2048, 6144, src=["norm"]),
        attention("attn", 1, 16, 4096, 128, src=["qkv"], causal=True,
                  rope_theta=1e6)])
    sched = solve(graph, hw)
    assert sched.valid
    for name, dim in (("attn", "K"), ("norm", "C")):
        scheme = sched.layer_schemes[name]
        assert scheme.levels[-1].tf(dim) == 1
        plan = lower_scheme(scheme, hw, repair=False)
        assert plan.valid, plan.reason
        assert plan.block[dim] == graph.by_name[name].dim(dim)


@pytest.mark.parametrize("preset", PRESET_NAMES)
def test_intra_solver_keeps_resident_dims_below_dram(preset):
    hw = PRESETS[preset]()
    for layer in (attention("a", 2, 8, 512, 256),
                  rmsnorm("n", 4096, 2048, 1e-6)):
        scheme, cost = solve_intra_layer(layer, hw)
        assert cost.valid
        for d in layer.resident_dims:
            assert scheme.levels[-1].tf(d) == 1
    # a row wider than the on-chip buffers holds comes back invalid, never
    # as a scheme that splits it at the DRAM level
    scheme, cost = solve_intra_layer(rmsnorm("wide", 64, 1 << 20, 1e-6), hw)
    assert scheme is None and not cost.valid
    assert "resident" in cost.reason


def test_attention_kv_priced_as_a_forwarded_activation():
    hw = PRESETS["eyeriss_multinode"]()
    layer = attention("a", 1, 16, 1024, 128, src=["qkv"], causal=True)
    scheme, _ = solve_intra_layer(layer, hw, Constraints(nodes=(16, 16)))
    cold = evaluate_layer(scheme, hw)
    warm = evaluate_layer(scheme, hw, src_onchip=True)
    # forwarded from qkv: neither Q nor the K/V pair comes from DRAM
    assert cold.dram_traffic_bytes > 0
    assert warm.dram_traffic_bytes == pytest.approx(
        scheme.fetches_into("O", 1) * layer.bytes_per_elem)
    assert layer.ifmap_size() == layer.tensor_size("I") \
        + layer.tensor_size("W")


def test_op_layers_scope_the_new_kinds(tiny):
    nplan, _ = tiny
    ops = fused_runner(nplan, cache=False).op_layers("outputs")
    kinds = {nplan.plans[n].kind for n in ops.values()}
    assert {"attention", "norm", "glu", "fc"} <= kinds
    assert "head" in ops.values()
