"""Distribution layer: sharding rules + a small-mesh lower/compile of the
real steps (subprocess so the forced device count never leaks into the
main test process)."""
import json
import os
import subprocess
import sys
import textwrap

import jax
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_config, list_archs
from repro.launch.sharding import leaf_spec, param_specs
from repro.launch.steps import input_specs, options_for, params_spec_struct
from repro.models.configs import INPUT_SHAPES


def test_param_specs_cover_all_archs():
    """Every leaf gets a spec, and sharded dims are divisible by 16."""
    for arch in list_archs():
        cfg = get_config(arch)
        tree = params_spec_struct(cfg)
        specs = param_specs(cfg, tree)
        flat_t = jax.tree_util.tree_leaves(tree)
        flat_s = jax.tree_util.tree_leaves(
            specs, is_leaf=lambda x: isinstance(x, P))
        assert len(flat_t) == len(flat_s)
        for leaf, spec in zip(flat_t, flat_s):
            for dim, ax in zip(leaf.shape, tuple(spec)):
                if ax is None:
                    continue
                axes = ax if isinstance(ax, tuple) else (ax,)
                size = 1
                for a in axes:
                    size *= 16
                assert dim % size == 0, (arch, leaf.shape, spec)


def test_serve_mode_drops_fsdp():
    cfg = get_config("yi-34b")
    tree = params_spec_struct(cfg)
    train = param_specs(cfg, tree, mode="train")
    serve = param_specs(cfg, tree, mode="serve")
    t = jax.tree_util.tree_leaves(train, is_leaf=lambda x: isinstance(x, P))
    s = jax.tree_util.tree_leaves(serve, is_leaf=lambda x: isinstance(x, P))
    assert any("data" in tuple(x) for x in t)
    assert not any("data" in tuple(x) for x in s)
    assert any("model" in tuple(x) for x in s)


def test_input_specs_shapes():
    for arch in ("qwen1.5-32b", "whisper-small", "internvl2-26b",
                 "mamba2-370m"):
        cfg = get_config(arch)
        for sname, shape in INPUT_SHAPES.items():
            sp = input_specs(cfg, shape)
            if shape.is_decode:
                assert sp["token"].shape == (shape.global_batch,)
            else:
                assert sp["tokens"].shape == (shape.global_batch,
                                              shape.seq_len)


def test_options_for_long_decode_is_subquadratic():
    cfg = get_config("yi-34b")
    opts = options_for(cfg, INPUT_SHAPES["long_500k"])
    assert opts.decode_window > 0
    assert options_for(cfg, INPUT_SHAPES["decode_32k"]).decode_window == 0


SUBPROCESS_PROG = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax
    from repro.configs import get_config
    from repro.launch.dryrun import build_args
    from repro.launch.mesh import make_mesh
    from repro.launch.sharding import to_shardings
    from repro.launch.steps import make_step, options_for
    from repro.models.configs import InputShape

    mesh = make_mesh((2, 4), ("data", "model"), jax.devices()[:8])
    cfg = get_config("{arch}").reduced(num_layers=2, d_model=256)
    cfg = cfg.with_updates(vocab_size=1024)
    shape = InputShape("mini", {seq}, {batch}, "{kind}")
    opts = options_for(cfg, shape)
    step = make_step(cfg, shape, opts)
    structs, in_specs, out_specs, donate = build_args(cfg, shape, mesh, opts)
    with mesh:
        compiled = jax.jit(step, in_shardings=to_shardings(in_specs, mesh),
                           out_shardings=to_shardings(out_specs, mesh),
                           donate_argnums=donate).lower(*structs).compile()
    print("COMPILED_OK", compiled.as_text().count(chr(10)) > 0)
""")


@pytest.mark.parametrize("arch,kind", [
    ("qwen1.5-32b", "train"), ("olmoe-1b-7b", "decode"),
    ("mamba2-370m", "prefill"), ("zamba2-1.2b", "decode"),
])
def test_reduced_step_compiles_on_8way_mesh(arch, kind):
    """Lower+compile the real step for a reduced config on a 2x4 mesh in a
    subprocess (device-count isolation)."""
    prog = SUBPROCESS_PROG.format(arch=arch, kind=kind,
                                  seq=64, batch=8)
    env = dict(os.environ, PYTHONPATH="src")
    r = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                       text=True, env=env, cwd=os.path.dirname(
                           os.path.dirname(os.path.abspath(__file__))),
                       timeout=600)
    assert "COMPILED_OK True" in r.stdout, r.stderr[-2000:]
