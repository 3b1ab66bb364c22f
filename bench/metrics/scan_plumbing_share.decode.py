"""Share of the traced `jit_decode_step` programs' device time spent in
operations under the layer scan's `layers` scope but outside the
`block` scope of its body: the scan's slicing, relayout and write-back
of the stacked weights and cache.  Averaged over the chips.

The trace names operations by HLO instruction only, so the decode
program is compiled again from shapes, as `greedy_decode` compiles it
(a persistent-cache hit where the cache is on), and each traced
operation is looked up in its text (`bench/lib/attribution.py`).  It
also prints, for the first chip, the shares of every region, the
operations the text lacks, and the ten operations that took most time
with their scopes."""
from statistics import mean

import jax
import jax.numpy as jnp

from bench.lib import attribution
from bench.run import load_module


def read(ctx, rec, t):
    found = attribution.decode_ops(t)
    if not found:
        return None
    driver = load_module("drivers", ctx.cell.traffic["driver"], ctx.cell.root / "bench")
    model, _, shapes, shardings = driver.build(ctx)
    params = jax.tree.map(lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
                          shapes, shardings)
    tr = ctx.cell.traffic
    scopes = attribution.op_scopes(attribution.decode_program_text(
        model, params, tr["batch"], tr["prompt_len"], rec["s_max"], jnp.bfloat16))
    if not any(attribution.region(s) == "scan" for s in scopes.values()):
        return None
    shares = {dev: attribution.time_by_region(spans, ops, scopes)
              for dev, (spans, ops) in found.items()}
    dev = sorted(shares)[0]
    spans, ops = found[dev]
    missing = sorted({e.name for e in ops if e.name not in scopes})
    top = {}
    for e in ops:
        top[e.name] = top.get(e.name, 0.0) + e.dur
    print(f"[scan_plumbing_share] {dev}: {len(spans)} steps, shares "
          + ", ".join(f"{k} {100 * v:.3f}%" for k, v in sorted(shares[dev].items()))
          + f"; {len(missing)} ops not in the text {missing[:10]}", flush=True)
    for name, s in sorted(top.items(), key=lambda x: -x[1])[:10]:
        print(f"[scan_plumbing_share]   {name} {s:.6f} s {scopes.get(name, '<missing>')!r}",
              flush=True)
    return 100.0 * mean(s.get("scan", 0.0) for s in shares.values())
