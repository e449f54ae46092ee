"""Compile-only checks of the chip path for a described TPU v5e.

The fused segment executables of ResNet-50 at batch 64 — the served
path's kernels at real width — are compiled for one chip of a described
``v5e:2x2`` topology: nothing runs, so these tests say nothing about
results or times, only that the chip's compiler accepts the programs and
that each fits the chip's 16 GB.  The topology is described inside a
fixture (never at import): only one process at a time may load the TPU
library, and every test worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.solver import solve
from repro.hw.presets import eyeriss_multinode
from repro.lower.exec import _run_fc, plan_runner
from repro.lower.fuse import FusedNetwork, input_specs
from repro.lower.netexec import network_runner
from repro.lower.netplan import lower_network
from repro.workloads.nets import get_net

V5E_HBM_BYTES = 16e9


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def resnet64():
    hw = eyeriss_multinode()
    graph = get_net("resnet", batch=64)
    nplan = lower_network(solve(graph, hw), graph, hw)
    assert not nplan.invalid_layers()
    return nplan


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip: keep the cache off."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _out_shape(layer):
    d = layer.dim
    if layer.kind == "fc":
        return (d("N"), d("K"))
    if layer.kind == "conv":
        return (d("N"), d("K"), d("X"), d("Y"))
    return (d("N"), d("C"), d("X"), d("Y"))          # pool, eltwise


def _segment_specs(nplan, fused, index, sharding):
    feeds = input_specs(nplan)
    specs = {}
    for name in fused.segment_io[index][0]:
        shape = feeds[name].shape if name in feeds \
            else _out_shape(nplan.plans[name].layer)
        specs[name] = jax.ShapeDtypeStruct(shape, jnp.float32,
                                           sharding=sharding)
    return specs


@pytest.mark.parametrize("layer", ["conv1", "r3b.add", "fc"])
def test_fused_segment_compiles_for_v5e(resnet64, one_chip, layer):
    """The segment holding ``layer``: the 7x7 stem conv + pool, a
    bottleneck with its residual add, and the classifier."""
    fused = FusedNetwork(resnet64)
    index = next(i for i, s in enumerate(resnet64.segments)
                 if layer in s.layer_names)
    state = _segment_specs(resnet64, fused, index, one_chip)
    compiled = fused._fn(("seg", index)).lower(state).compile()
    mem = compiled.memory_analysis()
    live = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert 0 < live < V5E_HBM_BYTES


def test_pallas_backend_refuses_misaligned_plans(resnet64, one_chip):
    """Eyeriss-sized blocks break the TPU's (8, 128) tiling: the pallas
    backend refuses them before compiling, naming the layer and block —
    and the chip's compiler agrees that the fc kernel cannot compile."""
    fc = resnet64.plans["fc"]
    with pytest.raises(ValueError, match=r"layer 'fc': W block \(2048, 200\)"):
        plan_runner(fc, backend="pallas")
    with pytest.raises(ValueError, match="layer 'conv1'"):
        network_runner(resnet64, {}, backend="pallas")
    x = jax.ShapeDtypeStruct((64, 2048), jnp.float32, sharding=one_chip)
    w = jax.ShapeDtypeStruct((2048, 1000), jnp.float32, sharding=one_chip)
    with pytest.raises(Exception, match="divisible"):
        jax.jit(lambda a, b: _run_fc(fc, a, b, False)).lower(x, w).compile()


def test_looplm_prefill_compiles_for_v5e(one_chip):
    """One Ouro-2.6B layer at its published widths, run twice with
    shared weights over a 4096-token sequence: the whole-net executable
    that returns the logits alone compiles for one chip, is fed one
    step's weights, and keeps the attention, norm and fc layers' scopes
    (XLA fuses every glu into the down matmul)."""
    hw = eyeriss_multinode()
    graph = get_net("looplm", batch=1, seq=4096, layers=1, steps=2)
    nplan = lower_network(solve(graph, hw), graph, hw)
    assert not nplan.invalid_layers()
    fused = FusedNetwork(nplan)
    specs = {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=one_chip)
             for k, v in input_specs(nplan).items()}
    weights = {k: v for k, v in specs.items() if k.endswith(".W")}
    acts = {k: v for k, v in specs.items() if not k.endswith(".W")}
    assert len(weights) == fused.weight_arrays == 10
    compiled = fused._fn(("net", "outputs", False)).lower(
        acts, weights).compile()
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes == 49152 * 4
    assert 0 < mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        < V5E_HBM_BYTES
    from repro.lower.fuse import hlo_op_layers
    kinds = {nplan.plans[n].kind
             for n in hlo_op_layers(compiled.as_text(), nplan.order).values()}
    assert {"attention", "norm", "fc"} <= kinds
