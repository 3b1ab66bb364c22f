"""Training launcher.

Builds the mesh from the available devices (production 16×16 / 2×16×16 on
real pods; ``--mesh DxM`` for an explicit debug mesh), shards state per
dist.sharding (ZeRO-1 optimizer state, DESIGN.md §12.2), and runs the
fault-tolerant driver (checkpoints, NaN rollback, signal save).

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
    PYTHONPATH=src python -m repro.launch.train --arch stablelm-3b \\
        --batch 8 --seq 128 --steps 50 --reduced --mesh 4x1

``--runtime`` shadow-dispatches each step's per-layer projection GEMM
bundle (M = batch·seq tokens) through the online concurrency runtime,
derated to the mesh's per-shard slot budget (DESIGN.md §12.5), and
returns its telemetry with the result.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.configs import get_arch
from repro.configs.shapes import InputShape
from repro.data.pipeline import DataLoader
from repro.dist.compress import compress_grads, ef_init
from repro.dist.fault_tolerance import FaultTolerantDriver, FTConfig
from repro.dist.resources import mesh_resources
from repro.dist.sharding import batch_pspecs, named, params_pspecs, zero1_pspecs
from repro.launch.mesh import make_debug_mesh
from repro.models import build_model
from repro.optim import AdamW, AdamWConfig
from repro.train.train_loop import TrainState, make_train_step, train_init


def make_mesh_from_devices() -> Mesh:
    devs = jax.devices()
    n = len(devs)
    model = 1
    for m in (16, 8, 4, 2, 1):
        if n % m == 0 and m <= n:
            model = m
            break
    data = n // model
    return Mesh(np.asarray(devs).reshape(data, model), ("data", "model"))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--reduced", action="store_true",
                    help="train the reduced (smoke) config of the arch")
    ap.add_argument("--mesh", default=None, metavar="DxM",
                    help="explicit debug mesh, e.g. 4x1 (ZeRO-1 over "
                         "data=4); default: auto from devices")
    ap.add_argument("--runtime", action="store_true",
                    help="shadow-dispatch step GEMMs via repro.runtime "
                         "with the mesh-derated slot budget")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if args.mesh:
        data, tp = (int(x) for x in args.mesh.lower().split("x"))
        mesh = make_debug_mesh(data, tp)
    else:
        mesh = make_mesh_from_devices()
    res = mesh_resources(mesh)
    model = build_model(cfg, mesh=mesh)
    opt = AdamW(AdamWConfig(lr=args.lr, total_steps=args.steps,
                            warmup_steps=max(args.steps // 20, 5)))

    state = train_init(model, opt, jax.random.PRNGKey(0))
    n_params = sum(x.size for x in jax.tree.leaves(state.params))
    p_specs = params_pspecs(model, mesh)
    z_specs = zero1_pspecs(model, mesh)
    from jax.sharding import PartitionSpec as P
    state_specs = TrainState(
        p_specs, type(state.opt)(P(), z_specs, z_specs), P()
    )

    if args.compress_grads:
        # EF is real training state: thread it through the jitted step
        # (a closure-mutated buffer would bake the first trace's zeros in
        # as a constant and leak tracers on retrace) and checkpoint it
        # with the rest of the carry.
        carry = (state, ef_init(state.params))
        carry_specs = (state_specs, p_specs)

        def step_fn(c, batch):
            st, ef = c
            box = {}

            def gt(g):
                gq, box["ef"] = compress_grads(g, ef)
                return gq

            base = make_train_step(
                model, opt, n_microbatches=args.microbatches,
                grad_transform=gt,
            )
            new_st, metrics = base(st, batch)
            return (new_st, box["ef"]), metrics
    else:
        carry = state
        carry_specs = state_specs
        step_fn = make_train_step(
            model, opt, n_microbatches=args.microbatches,
        )

    carry = jax.device_put(carry, named(mesh, carry_specs))
    shape = InputShape("cli", args.seq, args.batch, "train")
    loader = DataLoader(cfg, shape)

    inner = jax.jit(
        step_fn,
        out_shardings=(named(mesh, carry_specs), None),
        donate_argnums=(0,),
    )

    runtime = None
    step_requests = []
    if args.runtime:
        from repro.runtime import Runtime, decode_step_requests
        runtime = Runtime()
        # the runtime's own derating is authoritative (it knows its
        # controller's max_cd/spec) — report ITS budget, not a recompute
        res = runtime.set_mesh(mesh)
        # One training step's per-layer projection GEMMs see M = B·T
        # tokens; the bundle is shape-static, so derive it once.
        step_requests = decode_step_requests(
            runtime.ctrl, cfg, args.batch * args.seq
        )
        runtime.prewarm([r.desc for r in step_requests])
        print(f"[train] runtime derated: model_shards={res.model_shards} "
              f"slot_budget={res.slot_budget}")

    def jit_step(c, batch):
        if runtime is not None:
            for r in step_requests:
                runtime.submit(r, tenant=cfg.name)
            runtime.flush(force=True)
        batch = jax.device_put(
            batch, named(mesh, batch_pspecs(batch, mesh))
        )
        return inner(c, batch)

    driver = FaultTolerantDriver(
        jit_step, carry,
        FTConfig(ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every),
    )
    start = driver.maybe_restore()
    print(f"[train] {cfg.name}: {n_params:,} params, "
          f"mesh={dict(mesh.shape)}, per-shard frac={res.frac:.2f}, "
          f"cd_slots={res.slot_budget}, start_step={start}")

    t0 = time.time()
    result = driver.run(loader, args.steps, start_step=start)
    dt = time.time() - t0
    losses = result["losses"]
    if losses:
        print(f"[train] steps={result['final_step']} loss {losses[0]:.3f} -> "
              f"{losses[-1]:.3f} ({dt:.1f}s, p95 step {result['p95_s']*1e3:.0f}ms, "
              f"rollbacks={result['rollbacks']})")
    if runtime is not None:
        summary = runtime.telemetry.summary()
        result["telemetry"] = summary
        result["slot_budget"] = res.slot_budget
        print(f"[train] runtime telemetry: {summary}")
    loader.close()
    return result


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
