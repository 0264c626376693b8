"""Engine: memory planner (property-based), remat ladder, quantization,
fusion accounting, parallel plan bounds."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
import hypothesis.strategies as st
from hypothesis import given, settings

from repro.configs import get_config
from repro.engine import (POLICY_LADDER, activation_bytes, choose_policy,
                          compression_error, fuse_graph, greedy_no_reuse,
                          peak_live_bytes, plan_memory, plan_parallelism,
                          quantize_int4, quantize_int8, dequantize_int8,
                          dequantize_int4, sub_batch_split, swap_plan,
                          backprop_reorder_savings, Swapper)
from repro.engine.swap import _to_memory
from repro.offload import Graph, OpNode, build_model_graph

CFG = get_config("paper-backbone")
G = build_model_graph(CFG, 1, 128)


# -------------------------------------------------------- memory planner ---
def test_memory_plan_valid_and_bounded():
    plan = plan_memory(G)
    plan.validate()  # raises on temporal+address overlap
    assert plan.peak_bytes <= plan.naive_bytes
    assert plan.peak_bytes >= peak_live_bytes(G) - 1  # cannot beat liveness


@st.composite
def chain_graphs(draw):
    n = draw(st.integers(3, 20))
    nodes = []
    names = ["x"]
    for i in range(n):
        # random fan-in from earlier tensors; random sizes
        k = draw(st.integers(1, min(2, len(names))))
        ins = tuple(draw(st.sampled_from(names)) for _ in range(k))
        size = draw(st.integers(1, 10_000))
        nodes.append(OpNode(f"n{i}", "add", ins, f"n{i}", out_bytes=size))
        names.append(f"n{i}")
    return Graph(nodes=nodes, inputs=("x",), outputs=(names[-1],))


@settings(max_examples=40, deadline=None)
@given(chain_graphs())
def test_memory_plan_property(g):
    plan = plan_memory(g, alignment=1)
    plan.validate()
    assert plan.peak_bytes <= greedy_no_reuse(g)
    assert plan.peak_bytes >= peak_live_bytes(g)


# ----------------------------------------------------------------- remat ---
def test_remat_ladder_monotone():
    bases = [keep for _, keep, _ in POLICY_LADDER]
    assert bases == sorted(bases, reverse=True)
    overheads = [o for _, _, o in POLICY_LADDER]
    assert overheads == sorted(overheads)


def test_choose_policy_progressive():
    full = activation_bytes(CFG, 8, 512)
    d = choose_policy(CFG, 8, 512, budget_bytes=full * 2)
    assert d.policy == "none"
    d = choose_policy(CFG, 8, 512, budget_bytes=full * 0.5)
    assert d.policy == "dots"
    d = choose_policy(CFG, 8, 512, budget_bytes=full * 0.01)
    assert d.policy == "full"


def test_sub_batch_split_fits_budget():
    budget = activation_bytes(CFG, 1, 512) * 0.08 * 2.5  # fits ~2 examples
    n = sub_batch_split(CFG, 8, 512, budget, policy="full")
    per = activation_bytes(CFG, 8 // n, 512) * 0.08
    assert per <= budget


# ---------------------------------------------------------- quantization ---
@settings(max_examples=25, deadline=None)
@given(st.integers(0, 10_000), st.floats(0.1, 100.0))
def test_int8_roundtrip_bound(seed, scale):
    x = jax.random.normal(jax.random.PRNGKey(seed), (4, 256)) * scale
    q, s = quantize_int8(x)
    y = dequantize_int8(q, s, jnp.float32)
    blockmax = jnp.max(jnp.abs(x.reshape(4, 2, 128)), -1, keepdims=True)
    bound = jnp.repeat(blockmax / 127.0, 128, -1).reshape(4, 256) * 0.51 + 1e-9
    assert bool(jnp.all(jnp.abs(y - x) <= bound))


def test_int4_worse_than_int8():
    x = jax.random.normal(jax.random.PRNGKey(3), (8, 384))
    assert compression_error(x, 4) > compression_error(x, 8)
    assert compression_error(x, 8) < 0.02


def test_int4_pack_roundtrip():
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 256))
    packed, s = quantize_int4(x)
    assert packed.shape == (2, 128)
    y = dequantize_int4(packed, s, 256, jnp.float32)
    assert float(jnp.abs(y - x).max()) < float(jnp.abs(x).max()) * 0.2


# -------------------------------------------------------------- fusion -----
def test_fusion_preserves_flops_and_reduces_ops():
    g2, reports = fuse_graph(G)
    assert abs(g2.total_flops() - G.total_flops()) < 1e-6
    assert len(g2.nodes) < len(G.nodes)
    assert sum(r.bytes_saved for r in reports) > 0


# ------------------------------------------------------------- schedule ----
def test_parallel_plan_bounds():
    p1 = plan_parallelism(G, streams=1)
    p2 = plan_parallelism(G, streams=2)
    p4 = plan_parallelism(G, streams=4)
    assert 1.0 <= p2.speedup <= 2.0 + 1e-9
    assert p2.speedup <= p4.speedup + 1e-9
    assert abs(p1.speedup - 1.0) < 1e-6


def test_backprop_reorder_savings():
    full, reordered = backprop_reorder_savings(24, 10_000_000)
    assert full == 24 * reordered


def test_swapper_moves_through_host_memory():
    sw = Swapper(use_memory_kinds=True)
    x = jnp.arange(8.0)
    assert sw.offload("x", x).sharding.memory_kind == "pinned_host"
    y = sw.fetch("x")
    assert y.sharding.memory_kind == "device"
    np.testing.assert_array_equal(np.asarray(y), np.asarray(x))
    assert sw.total_bytes() == 2 * x.nbytes


def test_swapper_transfer_failure_raises():
    """A transfer that cannot happen is an error, not a silent stay."""
    with pytest.raises(ValueError):
        _to_memory(jnp.ones(4), "no_such_memory")


def test_swap_plan_meets_budget():
    per_layer = [100] * 10
    swapped, resident = swap_plan(per_layer, budget_bytes=450)
    assert resident <= 450
    assert swapped == list(range(6))  # earliest layers first
