"""Serving launcher: batched prefill + greedy decode over the mesh.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-14b --reduced \\
        --batch 4 --prompt-len 32 --gen 16

Weights are random (seeded) and made in bf16 directly on the devices that
hold them.  ``--runtime`` routes each decode step's QKV/FFN GEMMs through
the online concurrency runtime (`repro.runtime`, DESIGN.md §10) and prints
its telemetry summary (CD / mode mix / plan-cache hit rate, host µs per
dispatch phase, queue wait) after the run.
"""
from __future__ import annotations

import argparse
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs import get_arch
from repro.configs.shapes import InputShape
from repro.data.pipeline import make_batch
from repro.dist.sharding import named, params_pspecs
from repro.launch.train import make_mesh_from_devices
from repro.models import build_model
from repro.train.serve_loop import Decoded, greedy_decode


def init_params_sharded(model, mesh):
    """Random bf16 weights initialised under ``jit`` with the parameter
    shardings as outputs: each device makes only its own shard, so no
    device ever holds the whole model (or an f32 copy of it)."""
    shardings = named(mesh, params_pspecs(model, mesh))
    init = jax.jit(lambda key: model.init(key, jnp.bfloat16),
                   out_shardings=shardings)
    return init(jax.random.PRNGKey(0))


@dataclass
class Served:
    model: Any
    params: Any
    prompt: dict
    decoded: Decoded
    placed_bytes: list  # bytes_in_use per device (None entries off-TPU)


def main(argv=None) -> Served:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--runtime", action="store_true",
                    help="shadow-dispatch decode GEMMs via repro.runtime")
    ap.add_argument("--mixed-ops", action="store_true",
                    help="with --runtime: co-schedule the full decode op "
                         "bundle (attention/MoE/scan + GEMMs) as one "
                         "heterogeneous group (DESIGN.md §14)")
    ap.add_argument("--graph", action="store_true",
                    help="with --runtime: submit each decode step as a "
                         "dependency graph (QKV -> attention -> O-proj -> "
                         "FFN/MoE) and let the dataflow executor order it "
                         "(DESIGN.md §19)")
    args = ap.parse_args(argv)

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    mesh = make_mesh_from_devices()
    model = build_model(cfg, mesh=mesh)
    params = jax.block_until_ready(init_params_sharded(model, mesh))
    # device memory right after the weights are placed (None off-TPU)
    placed = [(d.memory_stats() or {}).get("bytes_in_use")
              for d in mesh.devices.flat]

    shape = InputShape("serve", args.prompt_len, args.batch, "prefill")
    prompt = make_batch(cfg, shape, 0)
    prompt.pop("labels", None)

    runtime = None
    if args.runtime:
        from repro.runtime import Runtime
        runtime = Runtime()
        # Derate available CD slots + cost-model spec to the per-shard
        # fraction of the serving mesh (DESIGN.md §12.5).
        res = runtime.set_mesh(mesh)
        print(f"[serve] runtime derated for mesh={dict(mesh.shape)}: "
              f"per-shard frac={res.frac:.2f} slot_budget={res.slot_budget}")

    out = greedy_decode(
        model, params, prompt, s_max=args.prompt_len + args.gen + 1,
        steps=args.gen, cache_dtype=jnp.bfloat16, runtime=runtime,
        tenant=cfg.name, mixed_ops=args.mixed_ops, graph=args.graph,
    )
    print(f"[serve] {cfg.name}: batch={args.batch} prompt={args.prompt_len} "
          f"gen={args.gen} -> {out.tokens.shape}; compile {out.compile_s:.2f}s, "
          f"prefill {out.prefill_s:.3f}s, decode {out.decode_s:.3f}s "
          f"({args.batch * args.gen / out.decode_s:.1f} tok/s)")
    print("first sequence:", out.tokens[0].tolist())
    if runtime is not None:
        print(f"[serve] runtime telemetry: {runtime.telemetry.summary()}")
    return Served(model, params, prompt, out, placed)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
