"""Roofline time of the decode step's Mamba2 layers (the bytes they must
move, `flops_hybrid.ssm_decode_bytes`: their weights once, the conv
inputs and the SSM state read and written once) at the peak HBM
bandwidth, over the device time of the operations under the program's
`mamba` scope in the traced `jit_decode_step` programs.  Averaged over
the chips.  The scopes come from the decode program's compiled text, as
`scan_plumbing_share.decode` reads them; the shape count is checked
against the served cache's bytes first."""
from statistics import mean

import jax
import jax.numpy as jnp

from bench.lib import attribution, flops_hybrid as fh
from bench.run import load_module


def decode_scopes(ctx, rec):
    """Instruction name → scope of the decode program as served, compiled
    once a run."""
    if "decode_scopes" not in ctx.extra:
        driver = load_module("drivers", ctx.cell.traffic["driver"], ctx.cell.root / "bench")
        model, _, shapes, shardings = driver.build(ctx)
        params = jax.tree.map(lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
                              shapes, shardings)
        tr = ctx.cell.traffic
        ctx.extra["decode_scopes"] = attribution.op_scopes(attribution.decode_program_text(
            model, params, tr["batch"], tr["prompt_len"], rec["s_max"], jnp.bfloat16))
    return ctx.extra["decode_scopes"]


def read(ctx, rec, t):
    found = attribution.decode_ops(t)
    if not found:
        return None
    m, B = ctx.cell.model, ctx.cell.traffic["batch"]
    fh.check_cache(m, B, rec["s_max"], rec["cache_bytes"])
    scopes = decode_scopes(ctx, rec)
    roof = fh.roofline_s(0, fh.ssm_decode_bytes(m, B), ctx.peaks)
    shares = []
    for spans, ops in found.values():
        dev = sum(e.dur for e in ops if "mamba" in scopes.get(e.name, "").split("/"))
        if dev:
            shares.append(len(spans) * roof / dev)
    if not shares:
        return None
    print(f"[ssm_roofline.decode] {fh.ssm_decode_bytes(m, B)} bytes a step, "
          f"{1e3 * roof:.3f} ms at peak", flush=True)
    return 100.0 * mean(shares)
