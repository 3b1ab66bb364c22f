"""Serve a (reduced) DeepSeek-V2 MoE with batched requests.

The routed-expert FFNs are GOLDYLOC's concurrent-GEMM pool: each decode step
dispatches the active experts as one grouped GEMM at the GO tile config for
that concurrency degree.

    PYTHONPATH=src python examples/serve_moe.py --batch 4 --gen 12
"""
import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import get_arch
from repro.configs.shapes import InputShape
from repro.data.pipeline import make_batch
from repro.models import build_model
from repro.train.serve_loop import greedy_decode


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=12)
    ap.add_argument("--mixed-ops", action="store_true",
                    help="co-schedule the full decode op bundle (GEMMs + "
                         "MLA attention + MoE grouped-GEMM) as one "
                         "heterogeneous concurrent group (DESIGN.md §14)")
    args = ap.parse_args(argv)

    cfg = get_arch("deepseek-v2-lite-16b").reduced()
    model = build_model(cfg, moe_capacity_factor=8.0)
    params = model.init(jax.random.PRNGKey(0))
    print(f"[serve_moe] {cfg.name}: MLA kv_lora={cfg.kv_lora_rank}, "
          f"{cfg.n_routed_experts} routed + {cfg.n_shared_experts} shared "
          f"experts, top-{cfg.moe_top_k}")

    shape = InputShape("serve", args.prompt_len, args.batch, "prefill")
    prompt = make_batch(cfg, shape, 0)
    prompt.pop("labels")

    # Shadow-dispatch each decode step's expert/attention GEMMs through the
    # online concurrency runtime (DESIGN.md §10) and report what it did.
    from repro.runtime import Runtime
    runtime = Runtime()

    t0 = time.time()
    toks = greedy_decode(
        model, params, prompt,
        s_max=args.prompt_len + args.gen + 1, steps=args.gen,
        runtime=runtime, tenant=cfg.name, mixed_ops=args.mixed_ops,
    ).tokens
    dt = time.time() - t0
    print(f"[serve_moe] batch={args.batch} prompt={args.prompt_len} "
          f"gen={args.gen}: {args.batch * args.gen / dt:.1f} tok/s")
    tele = runtime.telemetry.summary()
    print(f"[serve_moe] runtime: mean CD {tele['mean_cd']}, modes "
          f"{tele['modes']}, plan-cache hit rate "
          f"{tele['plan_cache_hit_rate']:.2f}")
    print(f"[serve_moe] sample continuation: {toks[0].tolist()}")
    assert toks.shape == (args.batch, args.gen)
    assert bool(jnp.isfinite(toks).all())
    print("[serve_moe] OK")


if __name__ == "__main__":
    main()
