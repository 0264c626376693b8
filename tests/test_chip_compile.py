"""Compile the served path's Pallas kernel for a described TPU v5e chip.

Nothing runs: the TPU compiler, installed beside the CPU backend,
compiles for a chip that is described and not attached, and refuses
what the chip would refuse (block shapes off the tiling, too much fast
memory) — which interpret mode cannot show.  The topology is described
inside a fixture, never at import, so every test worker collects the
same tests and only the one given this file loads the TPU library.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import ops
from repro.kernels import mamba_decode
from repro.kernels.paged_decode_attn import (KERNEL_NAME,
                                             paged_decode_attention)
from repro.models.model import (init_paged_pool, init_paged_slot_cache,
                                init_params)
from repro.models.runtime import DEFAULT_OPTIONS
from repro.serving.compile_cache import ServePrograms

PAPER = get_config("paper-backbone")
BLOCK = 16
SLOTS = 8
MARK = "tpu_custom_call"

# (heads, kv heads, head dim, blocks per slot, slots): paper-backbone at
# its registered max_seq_len, one GQA geometry of a larger served model,
# and Yi-34B's (group 7) as the chip benchmark serves it, 32 slots of
# 2304 tokens swept 8 blocks per grid step
GEOMETRIES = {
    "paper": (PAPER.num_heads, PAPER.num_kv_heads, PAPER.resolved_head_dim,
              PAPER.max_seq_len // BLOCK, SLOTS),
    "gqa": (32, 8, 128, 64, SLOTS),
    "yi-34b": (56, 8, 128, 2304 // BLOCK, 32),
}


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # else libtpu logs to /tmp
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:               # no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _kernel_args(one_chip, geometry, kv):
    h, kvh, hd, mb, slots = GEOMETRIES[geometry]
    nb = slots * mb + 1

    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    kv_dt = jnp.int8 if kv == "int8" else jnp.bfloat16
    args = [s((slots, h, hd), jnp.bfloat16),
            s((nb, BLOCK, kvh, hd), kv_dt), s((nb, BLOCK, kvh, hd), kv_dt),
            s((slots, mb), jnp.int32), s((slots,), jnp.int32),
            s((slots, kvh, hd), jnp.bfloat16),
            s((slots, kvh, hd), jnp.bfloat16)]
    scales = ([s((nb, BLOCK), jnp.float32)] * 2 if kv == "int8"
              else [None, None])
    return args + scales


@pytest.mark.parametrize("kv", ["bf16", "int8"])
@pytest.mark.parametrize("geometry", list(GEOMETRIES))
def test_paged_decode_kernel_compiles(one_chip, geometry, kv):
    def kernel(q, kb, vb, tb, pos, kn, vn, ks, vs):
        return paged_decode_attention(q, kb, vb, tb, pos, kn, vn,
                                      k_scale=ks, v_scale=vs)

    compiled = jax.jit(kernel).lower(
        *_kernel_args(one_chip, geometry, kv)).compile()
    assert MARK in compiled.as_text()


@pytest.mark.parametrize("kv", ["bf16", "int8"])
def test_paged_attention_op_picks_kernel_for_tpu(one_chip, kv):
    """The served path's op lowers to the kernel for a TPU — no flag."""
    compiled = ops.paged_attention.lower(
        *_kernel_args(one_chip, "paper", kv)).compile()
    assert MARK in compiled.as_text()


@pytest.mark.parametrize("kv_dtype", ["auto", "int8"])
def test_paged_decode_step_compiles(one_chip, kv_dtype):
    """The engine's own paged decode program with the kernel step, at
    paper-backbone's full width over a 2048-token slot."""
    opts = DEFAULT_OPTIONS.replace(paged_kernel=True, kv_dtype=kv_dtype)
    max_seq = PAPER.max_seq_len
    nb = SLOTS * (max_seq // BLOCK) + 1

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(
        lambda: init_params(PAPER, jax.random.PRNGKey(0))))
    cache = on_chip(jax.eval_shape(
        lambda: init_paged_slot_cache(PAPER, SLOTS, max_seq, opts)))
    pool = on_chip(jax.eval_shape(
        lambda: init_paged_pool(PAPER, nb, BLOCK, opts)))
    tokens = jax.ShapeDtypeStruct((SLOTS,), jnp.int32, sharding=one_chip)
    tables = jax.ShapeDtypeStruct((SLOTS, max_seq // BLOCK), jnp.int32,
                                  sharding=one_chip)
    step, _ = ServePrograms(PAPER, opts, max_seq).paged_decode(nb, BLOCK)
    compiled = step.lower(params, cache, pool, tokens, tables).compile()
    text = compiled.as_text()
    assert MARK in text
    # stable names: the step's module, and the kernel inside it
    assert "jit_paged_decode" in text
    assert KERNEL_NAME in text


# a computation's header, any reference to a computation, a conditional's
# branches, and the result shape of a sort or scatter instruction
_HEADER = re.compile(r"^(?:ENTRY\s+)?%([\w.\-]+)\s*\(")
_REF = re.compile(r"%([\w.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}"
                       r"|(?:true|false)_computation=%([\w.\-]+)")
_SORT_OR_SCATTER = re.compile(r"=\s*(.*?)\s(?:sort|scatter)\(")


def _wide_ops_by_place(text, widths):
    """The ``sort`` and ``scatter`` instructions of compiled HLO ``text``
    with a dimension in ``widths``, split into those inside a
    conditional's branch computations (or what they call) and the rest."""
    body, cur = {}, None
    for line in text.splitlines():
        m = _HEADER.match(line)
        if m and line.rstrip().endswith("{"):
            cur = m.group(1)
            body[cur] = []
        elif line.startswith("}"):
            cur = None
        elif cur is not None:
            body[cur].append(line)
    todo = [name for lines in body.values() for line in lines
            for group, one in _BRANCHES.findall(line)
            for name in _REF.findall(group) + ([one] if one else [])]
    in_branch = set()
    while todo:
        name = todo.pop()
        if name in body and name not in in_branch:
            in_branch.add(name)
            todo += [r for line in body[name] for r in _REF.findall(line)]
    inside, outside = [], []
    for name, lines in body.items():
        for line in lines:
            m = _SORT_OR_SCATTER.search(line)
            dims = {int(d) for shape in re.findall(r"\[([\d,]+)\]",
                                                   m.group(1) if m else "")
                    for d in shape.split(",") if d}
            if dims & widths:
                (inside if name in in_branch else outside).append(
                    line.strip()[:120])
    return inside, outside


@pytest.mark.parametrize("paged_kernel", [True, False])
def test_paged_decode_sorts_vocab_only_in_a_branch(one_chip, paged_kernel):
    """The paged decode program's sampler sorts the vocabulary only in the
    top-k branch of a conditional: an argmax-only batch never runs a sort
    or scatter as wide as the logits (a ``select`` in place of the
    ``cond`` would run every branch on every tick)."""
    opts = DEFAULT_OPTIONS.replace(paged_kernel=paged_kernel)
    max_seq = PAPER.max_seq_len
    nb = SLOTS * (max_seq // BLOCK) + 1

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(
        lambda: init_params(PAPER, jax.random.PRNGKey(0))))
    cache = on_chip(jax.eval_shape(
        lambda: init_paged_slot_cache(PAPER, SLOTS, max_seq, opts)))
    pool = on_chip(jax.eval_shape(
        lambda: init_paged_pool(PAPER, nb, BLOCK, opts)))
    tokens = jax.ShapeDtypeStruct((SLOTS,), jnp.int32, sharding=one_chip)
    tables = jax.ShapeDtypeStruct((SLOTS, max_seq // BLOCK), jnp.int32,
                                  sharding=one_chip)
    step, _ = ServePrograms(PAPER, opts, max_seq).paged_decode(nb, BLOCK)
    text = step.lower(params, cache, pool, tokens, tables).compile().as_text()
    vocab = PAPER.vocab_size
    inside, outside = _wide_ops_by_place(text, {vocab, SLOTS * vocab})
    assert outside == []
    assert inside                          # the top-k branch's sort


# Granite-4.0-H-Small as the chip benchmark serves it: the last stage's 10
# layers (m m m m m A m m m m) at full width, 9 of 72 experts, 128 slots
# of 2304 tokens; the estimate must leave room in the chip's 16 GB
GRANITE_SLOTS, GRANITE_SEQ = 128, 2304
CHIP_BYTES = 14e9


def test_mamba_decode_kernel_compiles(one_chip):
    """The state update at the cell's shape: 128 slots x 9 layers of 64
    rows of (128 x 128) float32, the state aliased in place."""
    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    slots, layers, rows, n, t = GRANITE_SLOTS, 9, 64, 128, 128
    compiled = jax.jit(mamba_decode.mamba_decode_step,
                       donate_argnums=(0,)).lower(
        s((slots, layers, rows, n, t), jnp.float32), s((), jnp.int32),
        s((slots, rows, t), jnp.bfloat16), s((slots, rows, t), jnp.float32),
        s((rows, t), jnp.float32), s((rows, t), jnp.float32),
        s((slots, n, t), jnp.float32),
        s((slots, n, t), jnp.float32)).compile()
    assert mamba_decode.KERNEL_NAME in compiled.as_text()
    # the state is the output's storage: nothing state-sized besides it
    assert compiled.memory_analysis().temp_size_in_bytes < 1e8


def test_granite_paged_decode_step_compiles(one_chip):
    """The engine's paged decode program for the Granite cell: both
    kernels by name in the compiled text, and the compiler's estimate of
    what it holds (weights, pool, slot state, temporaries) under 14 GB."""
    import json
    from pathlib import Path

    import granite_tiny
    conf = json.loads((Path(__file__).resolve().parents[1] / "benchmarks"
                       / "chip" / "configs"
                       / "granite-4.0-h-small-10l.json").read_text())
    fam = granite_tiny.family()
    cfg = fam.model_config(conf)
    opts = DEFAULT_OPTIONS.replace(paged_kernel=True)
    nb = GRANITE_SLOTS * (GRANITE_SEQ // BLOCK) + 1

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(lambda: fam.make_params(conf, 0)))
    cache = on_chip(jax.eval_shape(lambda: init_paged_slot_cache(
        cfg, GRANITE_SLOTS, GRANITE_SEQ, opts)))
    pool = on_chip(jax.eval_shape(lambda: init_paged_pool(cfg, nb, BLOCK,
                                                          opts)))
    tokens = jax.ShapeDtypeStruct((GRANITE_SLOTS,), jnp.int32,
                                  sharding=one_chip)
    tables = jax.ShapeDtypeStruct((GRANITE_SLOTS, GRANITE_SEQ // BLOCK),
                                  jnp.int32, sharding=one_chip)
    step, _ = ServePrograms(cfg, opts, GRANITE_SEQ).paged_decode(nb, BLOCK)
    compiled = step.lower(params, cache, pool, tokens, tables).compile()
    text = compiled.as_text()
    assert KERNEL_NAME in text and mamba_decode.KERNEL_NAME in text
    m = compiled.memory_analysis()
    assert m.argument_size_in_bytes + m.temp_size_in_bytes < CHIP_BYTES
