"""Compile a cell's serving programs for a described TPU v5e chip, with no
chip attached, and print what each one asks of the chip's memory.

  JAX_PLATFORMS=cpu python benchmarks/chip/compile_v5e.py <workload>...

For each workload: the paged decode step (which must hold the Pallas
kernel, ``tpu_custom_call``) and the burst prefill for every prompt bucket
and burst size the mix reaches, at the cell's sizes and the model's
weights' shapes.  Nothing runs: the numbers are the compiler's
``memory_analysis()``, not a measurement.
"""
from __future__ import annotations

import json
import math
import os
import sys
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
CHIP = Path(__file__).resolve().parent
sys.path.insert(0, str(CHIP))
sys.path.insert(0, str(CHIP / "traffic"))

import harness  # noqa: E402

MARK = "tpu_custom_call"


def _gb(n) -> float:
    return round(n / 1e9, 3)


def compile_cell(workload: str) -> dict:
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from repro.models.runtime import DEFAULT_OPTIONS

    jax.config.update("jax_enable_compilation_cache", False)
    bench = harness.load_benchmark()
    cell, config, mix = harness.load_cell(bench, workload)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    dev = SingleDeviceSharding(topo.devices[0])
    eng = mix["engine"]
    fam = harness.family(config)
    base = fam.model_config(config)
    opts = DEFAULT_OPTIONS.replace(paged_kernel=True,
                                   kv_cache_dtype=config["compute_dtype"])
    out = {"workload": workload, "slots": eng["slots"],
           "max_seq": eng["max_seq"], "programs": {}}
    _compile_programs(fam, base, config, mix, opts, dev, out)
    return out


def _compile_programs(fam, cfg, config, mix, opts, dev, out):
    import jax
    import jax.numpy as jnp
    import generator
    from repro.models.model import init_paged_pool, init_paged_slot_cache
    from repro.serving.compile_cache import ServePrograms
    eng = mix["engine"]
    slots, max_seq, bs = eng["slots"], eng["max_seq"], eng["block_size"]
    nb = slots * (max_seq // bs) + 1
    workload = out["workload"]

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=dev),
            tree)

    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=dev)

    params = on_chip(jax.eval_shape(lambda: fam.make_params(config, 0)))
    cache = on_chip(jax.eval_shape(
        lambda: init_paged_slot_cache(cfg, slots, max_seq, opts)))
    pool = on_chip(jax.eval_shape(
        lambda: init_paged_pool(cfg, nb, bs, opts)))
    progs = ServePrograms(cfg, opts, max_seq)

    def nbytes(tree):
        return _gb(sum(math.prod(a.shape) * a.dtype.itemsize
                       for a in jax.tree_util.tree_leaves(tree)))

    out["params_gb"] = nbytes(params)
    out["pool_gb"] = nbytes(pool)

    def record(name, compiled, need_kernel=False):
        m = compiled.memory_analysis()
        text = compiled.as_text()
        if need_kernel and MARK not in text:
            raise RuntimeError(f"{name}: no {MARK} in the program")
        out["programs"][name] = {
            "argument_gb": _gb(m.argument_size_in_bytes),
            "output_gb": _gb(m.output_size_in_bytes),
            "temp_gb": _gb(m.temp_size_in_bytes),
            "alias_gb": _gb(m.alias_size_in_bytes)}
        print(workload, name, out["programs"][name], flush=True)

    step, _ = progs.paged_decode(nb, bs)
    record("decode", step.lower(
        params, cache, pool, s((slots,), jnp.int32),
        s((slots, max_seq // bs), jnp.int32)).compile(), need_kernel=True)
    max_burst = mix.get("max_burst", slots)
    ks = [1 << i for i in range(int(math.log2(max_burst)) + 1)]
    for b in generator.buckets_of(mix):
        for k in ks:
            fn, _ = progs.paged_prefill_batch(b, k, nb, bs)
            record(f"prefill_b{b}_k{k}", fn.lower(
                params, cache, pool, s((k, b), jnp.int32),
                s((k,), jnp.int32), s((k, 2), jnp.uint32),
                s((k,), jnp.float32), s((k,), jnp.int32),
                s((k, b // bs), jnp.int32)).compile())


def main(argv):
    harness.import_program()
    results = [compile_cell(w) for w in argv]
    print(json.dumps(results))


if __name__ == "__main__":
    main(sys.argv[1:])
