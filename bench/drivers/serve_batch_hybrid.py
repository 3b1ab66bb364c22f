"""Closed loop of static batches through `greedy_decode`, for zamba2's
hybrid of Mamba2 layers and shared attention blocks.

The window, its timing, the runtime's `_Timed` wrapper and the choice of
the requests compared are `serve_batch`'s, loaded from its file and not
changed (see that driver for the traffic parameters).  What differs:

- ``KEYS``: the configuration's `run` keys and the program's names for
  them.  `build` fails on a key it does not know and on any value the
  program does not run as written, and on a ``hidden_act`` other than
  the one the program's MLP runs (``HIDDEN_ACT``).
- The weights: `weights.make`'s draws, except the Mamba2 mixer's 1-D
  leaves, which set its time scales and are drawn here as the published
  model initialises them (`mamba_leaves`): ``A_log`` = log(1 .. H) (A
  from -1 to -H), ``dt_bias`` the inverse softplus of a dt drawn
  log-uniform in [1e-3, 1e-1] from the seed, ``D`` ones.  N(0, 1) draws
  there would give time steps near 1 and decays of e^-H a step.  The
  reference reads the same arrays.
- The reference is `reference_zamba2`.
- The record holds the bytes of each kind of cache as the served arrays
  hold them (`Decoded.cache_bytes`).  Besides the token gap, `check`
  compares them with the configuration's cache, counted from shapes by
  `flops_hybrid.cache_bytes` (the SSM state in f32): ``cache_bytes_off``,
  the largest relative difference of a kind, limit 0.  The token gap
  cannot see a state kept in bf16 at these lengths; this does.
"""
from __future__ import annotations

import importlib.util
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from bench.lib import compare, flops_hybrid, reference_zamba2, weights

_spec = importlib.util.spec_from_file_location(
    "bench_drivers_serve_batch", Path(__file__).with_name("serve_batch.py"))
sb = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(sb)

# Keys of a configuration's `run` section and the program's names for them.
KEYS = {
    "family": "family", "n_layers": "n_layers", "d_model": "d_model",
    "n_heads": "n_heads", "n_kv_heads": "n_kv_heads", "head_dim": "resolved_head_dim",
    "d_ff": "d_ff", "vocab_size": "vocab_size", "rope_theta": "rope_theta",
    "norm_eps": "norm_eps", "attn_scale": "resolved_attn_scale",
    "tie_embeddings": "tie_embeddings", "ssm_state": "ssm_state",
    "ssm_head_dim": "ssm_head_dim", "ssm_expand": "ssm_expand", "ssm_conv": "ssm_conv",
    "ssm_ngroups": "ssm_ngroups", "ssm_dt_min": "ssm_dt_min",
    "hybrid_layer_ids": "hybrid_layer_ids", "num_mem_blocks": "num_mem_blocks",
    "adapter_rank": "adapter_rank", "concat_embed": "concat_embed",
}
HIDDEN_ACT = "gelu"        # the program's shared-block MLP: the exact GELU
DT_RANGE = (1e-3, 1e-1)   # the published dt initialisation's range
DT_FLOOR = 1e-4


def _same(got, want) -> bool:
    if isinstance(want, list):
        return list(got) == want
    if isinstance(want, float):
        return math.isclose(float(got), want, rel_tol=1e-9)
    return got == want


def build(ctx):
    """The program's model on the cell's mesh, with its parameter shapes
    and shardings; fails where the program's sizes are not the file's."""
    from jax.sharding import Mesh

    from repro.configs import get_arch
    from repro.dist.sharding import named, params_pspecs
    from repro.models import build_model

    conf = ctx.cell.config
    arch = get_arch(conf["arch"])
    if conf.get("hidden_act") != HIDDEN_ACT:
        raise ValueError(f"{conf['arch']}: the program's MLP runs hidden_act={HIDDEN_ACT!r}, "
                         f"the configuration file says {conf.get('hidden_act')!r}")
    for key, value in conf["run"].items():
        if key not in KEYS:
            raise ValueError(f"{conf['arch']}: the driver does not know the run key {key!r}")
        got = getattr(arch, KEYS[key])
        if not _same(got, value):
            raise ValueError(f"{conf['arch']}: the program runs {key}={got!r}, "
                             f"the configuration file says {value!r}")
    shape = (conf["mesh"]["data"], conf["mesh"]["model"])
    mesh = Mesh(np.asarray(ctx.devices).reshape(shape), ("data", "model"))
    model = build_model(arch, mesh=mesh)
    shapes = jax.eval_shape(lambda k: model.init(k, jnp.bfloat16), jax.random.PRNGKey(0))
    return model, mesh, shapes, named(mesh, params_pspecs(model, mesh))


def mamba_leaves(layers: int, heads: int, seed: int) -> dict:
    """A_log, dt_bias and D of every Mamba2 layer, (layers, heads) each."""
    k = jax.random.fold_in(weights.key_of(seed), 0x5A2B)
    lo, hi = math.log(DT_RANGE[0]), math.log(DT_RANGE[1])
    dt = jnp.exp(lo + (hi - lo) * jax.random.uniform(k, (layers, heads), jnp.float32))
    dt = jnp.maximum(dt, DT_FLOOR)
    return {"A_log": jnp.broadcast_to(jnp.log(jnp.arange(1, heads + 1, dtype=jnp.float32)),
                                      (layers, heads)),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "D": jnp.ones((layers, heads), jnp.float32)}


def make_weights(shapes, seed: int, shardings=None):
    """`weights.make`'s arrays, the Mamba2 mixers' 1-D leaves replaced by
    `mamba_leaves` (in the leaves' dtype and sharding)."""
    params = weights.make(shapes, seed, shardings)
    mixer = params["layers"]["mamba"]
    L, H = mixer["A_log"].shape
    for k, v in mamba_leaves(L, H, seed).items():
        mixer[k] = jax.device_put(v.astype(mixer[k].dtype), mixer[k].sharding)
    return params


def setup(ctx, warm: bool = True):
    tr = ctx.cell.traffic
    model, mesh, shapes, shardings = build(ctx)
    params = jax.block_until_ready(make_weights(shapes, ctx.seed, shardings))
    runtime, flush = None, {"calls": 0, "s": 0.0}
    if tr.get("runtime") == "shadow":
        from repro.runtime import Runtime

        runtime = Runtime()
        runtime.set_mesh(mesh)
        runtime.flush = sb._Timed(runtime.flush, "bench.flush", flush)
        runtime.drain = sb._Timed(runtime.drain, "bench.drain", flush)
    ctx.extra.update(shapes=shapes, shardings=shardings)
    state = {"model": model, "params": params, "runtime": runtime, "flush": flush,
             "s_max": tr["prompt_len"] + tr["out_len"]["max"] + 1, "cache_bytes": {}}
    if warm:
        # one short batch of the window's shapes compiles both programs and
        # tunes the runtime; the join of a full batch's tokens is the one
        # other program the window runs
        steps = tr["out_len"]["max"]
        dec = sb._serve(ctx, state, sb.prompts(ctx, sb.WARM), steps=2)
        state["cache_bytes"] = dict(dec.cache_bytes)
        jax.block_until_ready(jnp.concatenate([dec.tokens[:, :1]] * steps, axis=1))
    return state


def window(ctx, state):
    rec = sb.window(ctx, state)
    model = state["model"]
    rec["cache_bytes"] = state["cache_bytes"] or model.cache_nbytes(jax.eval_shape(
        lambda: model.init_cache(batch=ctx.cell.traffic["batch"], s_max=state["s_max"],
                                 dtype=jnp.bfloat16)))
    return rec


release = sb.release
end_to_end = sb.end_to_end


def reference_logits(ctx, rec, quant=None):
    """The reference's logits at every served position of a sample of the
    window's requests (``quant="fp8"``: the control), with the served
    tokens (-1 past each request's own length)."""
    picked = sb.pick(ctx, rec, ctx.cell.limits["sample"])
    toks, served = sb.reference_inputs(ctx, rec, picked)
    w = make_weights(ctx.extra["shapes"], ctx.seed, ctx.extra["shardings"])
    P = ctx.cell.traffic["prompt_len"]
    ref = reference_zamba2.logits(w, ctx.cell.model, toks, P - 1, served.shape[1])
    ctl = None if quant is None else reference_zamba2.logits(
        w, ctx.cell.model, toks, P - 1, served.shape[1], quant=quant)
    print(f"[serve_batch_hybrid] compared {int((served >= 0).sum())} served tokens of "
          f"{len(picked)} requests {picked} with the reference", flush=True)
    return ref, ctl, served


def check(ctx, rec):
    ref, _, served = reference_logits(ctx, rec)
    gap = float(compare.token_gaps(ref, served).max())
    stated = flops_hybrid.cache_bytes(ctx.cell.model, ctx.cell.traffic["batch"], rec["s_max"])
    off = max(abs(rec["cache_bytes"].get(k, 0) - v) / v for k, v in stated.items())
    return [("max_token_gap", gap, ctx.cell.limits["max_token_gap"]["limit"]),
            ("cache_bytes_off", off, ctx.cell.limits["cache_bytes_off"]["limit"])]
