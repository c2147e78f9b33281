"""Compile a cell's largest programs for a described v5e, without the chip.

    JAX_PLATFORMS=cpu python benchmarks/checks/compile_v5e.py <workload> [engine_key=int ...]

on-chip-measurement guide, section 2.3: the TPU compiler is installed in
the sandbox and compiles for a chip that is described, not attached.  The
scheduler's own program builders are lowered on ``jax.eval_shape`` weights
and a shape-only page pool placed on one described v5e device, with
``on_tpu()`` steered to true so the Pallas kernels arm.  It says whether
the program fits the chip and how long it compiles; it is never a chip run.
Weights, model configuration and the cache's buffers are the family's
(families/__init__.py), so a cell of any family compiles here as long as the
scheduler's prefill, decode and span programs take the family's cache buffers
in ``cache_buffers``' order.  Under a prefix cache the span program
(``jit_rpa_step``) is compiled too, at the two largest token buckets the
geometry admits: every slot a whole fresh prompt, and half of that, which is
what prompts behind a cached prefix fill (``internlm2-offline``: 32,768,
PERF.md section 5).
"""

from __future__ import annotations

import os
import sys
import time
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(HERE.parent), str(HERE)]


def main(workload: str, *overrides: str) -> int:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import lmrs_tpu.utils.platform as platform
    import run as bench_run

    jax.config.update("jax_enable_compilation_cache", False)
    platform.on_tpu = lambda: True  # steer the kernel gates (this script only)
    cell, bench, _ = bench_run.find_cell(workload)
    traffic = bench_run.read_json(HERE / "traffic" / f"{cell['traffic']}.json")
    fam, model = bench_run.families.of_config(
        HERE / "configs" / f"{cell['config']}.json")
    for kv in overrides:  # e.g. max_batch_slots=32: a what-if on the engine geometry
        k, v = kv.split("=", 1)
        traffic["engine"][k] = int(v)
    tok_mod = bench_run.load_module(HERE / "tokenizer.py", "bench_tokenizer")
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def on_chip(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=chip), tree)

    params = on_chip(fam.param_shapes(model))
    # the engine's constructor wants real weights: build the scheduler
    # directly, on shapes
    from lmrs_tpu.engine import scheduler as sched_mod

    eng = traffic["engine"]
    mcfg, ecfg, mesh_cfg = bench_run.make_configs(fam, cell["config"], model,
                                                  eng)
    if mesh_cfg is not None:
        raise SystemExit("compile_v5e.py compiles one-chip cells only: a "
                         "sharded cell's pool would have to be placed on "
                         "devices that are described, not attached")
    os.environ["LMRS_WATCHDOG"] = "0"
    sched = sched_mod.ContinuousScheduler(
        ecfg, mcfg, params, tok_mod.IdTokenizer(model["vocab_size"]))
    B, S = sched.B, eng["max_seq_len"]
    W = sched.cache.max_pages_per_slot
    bufs = [None if b is None else on_chip(jax.eval_shape(lambda b=b: b))
            for b in fam.cache_buffers(sched)]

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    i32, f32 = jnp.int32, jnp.float32
    key = sds((2,), jnp.uint32)
    print(f"{workload}: cache " + " + ".join(
        f"{b.shape} {b.dtype}" for b in bufs if b is not None) + " = "
          f"{fam.weight_bytes(bufs) / 2**30:.2f} GiB; weights "
          f"{fam.weight_bytes(params) / 2**30:.2f} GiB")
    progs = {
        f"prefill[{B}x{S}]": (sched._get_prefill_fn(S), (
            params, *bufs, sds((B,), i32),
            sds((B, S), i32), sds((B,), i32), sds((B,), i32), sds((B,), i32),
            sds((B, W), i32), key, sds((B,), f32), sds((B,), i32),
            sds((B,), f32))),
        f"decode[{B} rows, w={W}]": (sched._get_decode_fn(W), (
            params, *bufs, sds((B,), i32), sds((B,), i32),
            sds((B,), i32), sds((B, W), i32), sds((B,), jnp.bool_), key,
            sds((B,), f32), sds((B,), i32), sds((B,), f32))),
    }
    if sched._pc_on:  # the scheduler's own decision (off under int8 KV pages)
        # what a prompt behind a cached prefix runs in place of the prefill
        top = 1 << (B * (S - eng["max_tokens"]) - 1).bit_length()
        for T in (top, top // 2):
            progs[f"span step[{T} tokens, w={W}]"] = (sched._get_rpa_fn(T, W), (
                params, *bufs, sds((B,), i32), sds((1, T), i32),
                sds((B,), i32), sds((B,), i32), sds((T,), i32),
                sds((B,), i32), sds((B,), i32), sds((B, W), i32), key,
                sds((B,), f32), sds((B,), i32), sds((B,), f32)))
    for name, (fn, args) in progs.items():
        t0 = time.time()
        compiled = fn.lower(*args).compile()
        ma = compiled.memory_analysis()
        total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                 + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
        print(f"  {name}: compiled for {topo.devices[0].device_kind} in "
              f"{time.time() - t0:.1f}s; arguments "
              f"{ma.argument_size_in_bytes / 2**30:.2f} GiB, temporaries "
              f"{ma.temp_size_in_bytes / 2**30:.2f} GiB, in all "
              f"{total / 2**30:.2f} GiB", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(*sys.argv[1:]))
