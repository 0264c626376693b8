"""CPU rehearsal of ``chip_smoke.py``: its phases at a reduced config, the
Pallas kernel in interpret mode, the device check steered here; the
script as-is refusing the CPU; and the compile-cache helper it calls."""
import functools
import importlib.util
import os
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels import ops
from repro.launch.cache import DEFAULT_DIR, ENV_VAR, enable_compile_cache

ROOT = Path(__file__).resolve().parents[1]
_spec = importlib.util.spec_from_file_location("chip_smoke",
                                               ROOT / "chip_smoke.py")
smoke = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(smoke)

CFG = get_config("paper-backbone").with_updates(
    num_layers=2, d_model=64, num_heads=4, num_kv_heads=2, head_dim=16,
    d_ff=128, vocab_size=300)
# what an interpreted kernel leaves in its compiled program (the oracle
# leaves no such name)
INTERPRET_MARK = "_paged_decode_kernel"


@pytest.fixture
def interpret_kernel(monkeypatch):
    """Route the served path's paged attention op through the Pallas
    interpreter, and point the smoke's kernel check at its mark."""
    monkeypatch.setattr(ops, "paged_attention", jax.jit(
        functools.partial(ops.paged_attention, interpret=True),
        static_argnames="window"))
    monkeypatch.setattr(smoke, "KERNEL_MARK", INTERPRET_MARK)


def test_check_device_refuses_cpu():
    with pytest.raises(SystemExit, match="found platform 'cpu'"):
        smoke.check_device()


def test_check_device_reports_a_tpu(monkeypatch, capsys):
    chip = SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    monkeypatch.setattr(jax, "devices", lambda: [chip])
    assert smoke.check_device() == {"platform": "tpu",
                                    "kind": "TPU v5 lite", "count": 1}
    assert "TPU v5 lite x1" in capsys.readouterr().out


def test_kernel_phase(interpret_kernel, capsys):
    smoke.kernel_phase(CFG, slots=3, max_seq=64, block_size=16)
    out = capsys.readouterr().out
    assert "bf16 max|err|" in out and "int8 max|err|" in out


@pytest.fixture(scope="module")
def paper_cases():
    """The kernel phase's operands at its own (full) geometry, with the
    oracle's answer for each pool."""
    cases = smoke.kernel_cases(get_config("paper-backbone"))
    return {name: (args, smoke.oracle(args)) for name, args in cases.items()}


def _drop_block(args, slot, j):
    """``args`` as a kernel that skipped block ``j`` of ``slot`` reads
    them: the slot's later blocks move up one, and it holds a block's
    worth of tokens fewer."""
    q, kb, vb, tables, pos, *rest = args
    t, p = np.asarray(tables).copy(), np.asarray(pos).copy()
    j %= t.shape[1]
    t[slot, j:-1] = t[slot, j + 1:]
    p[slot] -= kb.shape[2]
    return (q, kb, vb, jnp.asarray(t), jnp.asarray(p), *rest)


# (slot, block): the first and last block of the fullest slot, and the
# block whose loss moves its slot's output least under the smoke's seed
DROPS = {"first": (-1, 0), "last": (-1, -1), "least_felt": (-2, 95)}


@pytest.mark.parametrize("drop", list(DROPS))
@pytest.mark.parametrize("pool", ["bf16", "int8"])
def test_kernel_check_rejects_a_dropped_block(paper_cases, pool, drop):
    args, want = paper_cases[pool]
    assert smoke.slot_rel_err(want, want) == 0.0
    broken = smoke.oracle(_drop_block(args, *DROPS[drop]))
    assert smoke.slot_rel_err(broken, want) > smoke.KERNEL_RTOL


def test_kernel_check_rejects_shifted_int8_scales(paper_cases):
    """K's row scales of one block read one row off."""
    args, want = paper_cases["int8"]
    q, kb, vb, tables, pos, kn, vn, layer, ks, vs = args
    blk = int(tables[-1, 50])
    ks = jnp.asarray(ks).at[blk, layer].set(jnp.roll(ks[blk, layer], 1))
    broken = smoke.oracle((q, kb, vb, tables, pos, kn, vn, layer, ks, vs))
    assert smoke.slot_rel_err(broken, want) > smoke.KERNEL_RTOL


@pytest.mark.parametrize("pool", ["bf16", "int8"])
def test_kernel_check_rejects_another_layer(paper_cases, pool):
    """A kernel that read the pool's first layer in place of the last."""
    args, want = paper_cases[pool]
    broken = smoke.oracle(args[:7] + (0,) + args[8:])
    assert smoke.slot_rel_err(broken, want) > smoke.KERNEL_RTOL


def test_oracle_program_holds_no_kernel(monkeypatch):
    """Unsteered on CPU the op lowers to the oracle, so the kernel check
    the smoke makes on the chip fails here."""
    monkeypatch.setattr(smoke, "KERNEL_MARK", INTERPRET_MARK)
    with pytest.raises(RuntimeError, match="holds no kernel"):
        smoke.kernel_phase(CFG, slots=2, max_seq=32, block_size=16)


def test_serve_phase(interpret_kernel, capsys):
    smoke.serve_phase(CFG, slots=2, max_seq=128, requests=6,
                      prompt_lens=(8, 40), max_new_tokens=8, adapt_every=4,
                      timed_steps=2)
    out = capsys.readouterr().out
    assert "48 tokens = 6 x 8" in out
    assert "[adapt]" in out


def test_parity_phase(interpret_kernel, capsys):
    smoke.parity_phase(CFG, slots=2, max_seq=64, requests=3,
                       prompt_lens=(8, 30), max_new_tokens=6)
    assert "token agreement" in capsys.readouterr().out


def test_script_refuses_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                       capture_output=True, text=True, env=env, cwd=ROOT,
                       timeout=300)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "found platform 'cpu'" in r.stderr


@pytest.fixture
def cache_dir_config():
    old = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", old)


def test_compile_cache_follows_env(monkeypatch, tmp_path, cache_dir_config):
    monkeypatch.setenv(ENV_VAR, str(tmp_path))
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)


def test_compile_cache_defaults_to_ignored_checkout_dir(monkeypatch,
                                                       cache_dir_config):
    monkeypatch.delenv(ENV_VAR, raising=False)
    assert enable_compile_cache() == str(ROOT / ".jax_cache")
    assert DEFAULT_DIR == ROOT / ".jax_cache"
    assert jax.config.jax_compilation_cache_dir == str(DEFAULT_DIR)
    ignored = (ROOT / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored
