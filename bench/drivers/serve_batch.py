"""Closed loop of static batches through the served entry, `greedy_decode`.

Traffic parameters (`bench/traffic/<mix>.json`): ``batch`` requests per
batch, ``prompt_len`` random prompt ids each, output lengths from
``out_len`` (a clipped lognormal drawn stratified, one request from each
of ``batch`` strata), and ``runtime: "shadow"`` to pass a `Runtime` in as
`repro.launch.serve --runtime` builds it.  Every batch is built with the
same cache capacity, prompt length plus the largest output length plus
one, so one prefill and one decode program serve the whole window.

Batches run back to back.  The window closes when the batch in flight at
``--seconds`` completes.  A request counts only its own output length,
not the steps its batch decodes past it.  The traced run traces the
first ``trace_s`` seconds of the window's first batch: its per-call
lowering, its prefill and as many decode steps as the rest of that time
holds.  Stopping the profiler stalls that batch, so the window of a
traced run never closes on it: at least one untraced batch follows, for
the metrics taken on the host clock.

After the window, a sample of the finished requests drawn from the seed
(the longest among them) is run through the float32 reference, with the
prompt and the served tokens as its input, and every served token's gap
to the reference's best logit is compared with the cell's limit.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from bench.lib import compare, reference, traffic, weights

# Keys of a configuration's `run` section and the program's names for them.
ARCH_KEYS = {
    "n_layers": "n_layers", "d_model": "d_model", "n_heads": "n_heads",
    "n_kv_heads": "n_kv_heads", "head_dim": "resolved_head_dim", "d_ff": "d_ff",
    "vocab_size": "vocab_size", "rope_theta": "rope_theta", "norm_eps": "norm_eps",
    "qk_norm": "qk_norm", "family": "family",
}
WARM = 2**31   # the batch index of the warm-up batch's prompts


def build(ctx):
    """The program's model on the cell's mesh, with its parameter shapes
    and shardings; fails where the program's sizes are not the file's."""
    from jax.sharding import Mesh

    from repro.configs import get_arch
    from repro.dist.sharding import named, params_pspecs
    from repro.models import build_model

    conf = ctx.cell.config
    arch = get_arch(conf["arch"])
    for key, value in conf["run"].items():
        got = getattr(arch, ARCH_KEYS[key])
        if got != value:
            raise ValueError(f"{conf['arch']}: the program runs {key}={got!r}, "
                             f"the configuration file says {value!r}")
    shape = (conf["mesh"]["data"], conf["mesh"]["model"])
    mesh = Mesh(np.asarray(ctx.devices).reshape(shape), ("data", "model"))
    model = build_model(arch, mesh=mesh)
    shapes = jax.eval_shape(lambda k: model.init(k, jnp.bfloat16), jax.random.PRNGKey(0))
    return model, mesh, shapes, named(mesh, params_pspecs(model, mesh))


class _Timed:
    """Host clock and a trace span around a bound method of one instance."""

    def __init__(self, fn, label, stats):
        self.fn, self.label, self.stats = fn, label, stats

    def __call__(self, *a, **kw):
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation(self.label):
            out = self.fn(*a, **kw)
        self.stats["calls"] += 1
        self.stats["s"] += time.perf_counter() - t
        return out


def setup(ctx, warm: bool = True):
    tr = ctx.cell.traffic
    model, mesh, shapes, shardings = build(ctx)
    params = jax.block_until_ready(weights.make(shapes, ctx.seed, shardings))
    runtime, flush = None, {"calls": 0, "s": 0.0}
    if tr.get("runtime") == "shadow":
        from repro.runtime import Runtime

        runtime = Runtime()
        runtime.set_mesh(mesh)
        runtime.flush = _Timed(runtime.flush, "bench.flush", flush)
        runtime.drain = _Timed(runtime.drain, "bench.drain", flush)
    ctx.extra.update(shapes=shapes, shardings=shardings)
    state = {"model": model, "params": params, "runtime": runtime, "flush": flush,
             "s_max": tr["prompt_len"] + tr["out_len"]["max"] + 1}
    if warm:
        # One short batch of the window's shapes compiles (or reads from the
        # persistent cache) both programs and tunes the runtime; the join of
        # a full batch's tokens is the one other program the window runs.
        steps = tr["out_len"]["max"]
        dec = _serve(ctx, state, prompts(ctx, WARM), steps=2)
        jax.block_until_ready(jnp.concatenate([dec.tokens[:, :1]] * steps, axis=1))
    return state


def prompts(ctx, i: int) -> np.ndarray:
    tr = ctx.cell.traffic
    return traffic.prompts(ctx.seed, i, tr["batch"], tr["prompt_len"],
                           ctx.cell.model["vocab_size"])


def _serve(ctx, state, ids, steps):
    from repro.train.serve_loop import greedy_decode

    with jax.profiler.TraceAnnotation("bench.greedy_decode"):
        return greedy_decode(
            state["model"], state["params"], {"tokens": jnp.asarray(ids)},
            s_max=state["s_max"], steps=steps, cache_dtype=jnp.bfloat16,
            runtime=state["runtime"], tenant=ctx.cell.config["arch"])


def window(ctx, state):
    tr = ctx.cell.traffic
    batches = []
    t0 = time.perf_counter()
    while True:
        i = len(batches)
        lens = traffic.batch_lengths(tr["out_len"], ctx.seed, i, tr["batch"])
        steps = max(lens)
        f0 = dict(state["flush"])
        start = time.perf_counter()
        traced = ctx.trace and i == 0
        if traced:
            ctx.tracer.start(tr["trace_s"])
        dec = _serve(ctx, state, prompts(ctx, i), steps)
        ctx.tracer.wait()
        t = time.perf_counter()
        batches.append({
            "lens": lens, "steps": steps, "compile_s": dec.compile_s,
            "prefill_s": dec.prefill_s, "decode_s": dec.decode_s, "wall_s": t - start,
            "tokens": np.asarray(dec.tokens), "traced": traced,
            "flush_calls": state["flush"]["calls"] - f0["calls"],
            "flush_s": state["flush"]["s"] - f0["s"],
        })
        if t - t0 >= ctx.seconds and not traced:
            break
    elapsed = t - t0
    n = sum(len(b["lens"]) for b in batches)
    useful = sum(sum(b["lens"]) for b in batches)
    print(f"[serve_batch] {len(batches)} batches, {n} requests, {useful} useful "
          f"tokens of {sum(b['steps'] for b in batches) * tr['batch']} decoded, "
          f"in {elapsed:.3f} s", flush=True)
    return {"batches": batches, "elapsed_s": elapsed, "attempted": n, "failed": 0,
            "useful_tokens": useful, "s_max": state["s_max"]}


def release(state):
    state.pop("params", None)
    state.pop("runtime", None)


def end_to_end(ctx, rec):
    steps = sum(b["steps"] for b in rec["batches"])
    return {"tok_s": rec["useful_tokens"] / rec["elapsed_s"],
            "tpot_ms": 1e3 * sum(b["decode_s"] for b in rec["batches"]) / steps}


def served_requests(rec):
    """(batch index, row, output length) of every request of the window."""
    return [(i, r, n) for i, b in enumerate(rec["batches"]) for r, n in enumerate(b["lens"])]


def reference_inputs(ctx, rec, picked):
    """Prompt plus served tokens of each picked request, padded, and the
    served tokens (-1 past each request's own length)."""
    P = ctx.cell.traffic["prompt_len"]
    L = max(n for _, _, n in picked)
    toks = np.zeros((len(picked), P + L - 1), np.int32)
    served = np.full((len(picked), L), -1, np.int32)
    for j, (i, r, n) in enumerate(picked):
        toks[j, :P] = prompts(ctx, i)[r]
        out = rec["batches"][i]["tokens"][r, :n]
        toks[j, P:P + n - 1] = out[:n - 1]
        served[j, :n] = out
    return toks, served


def pick(ctx, rec, k):
    reqs = served_requests(rec)
    longest = max(range(len(reqs)), key=lambda j: reqs[j][2])
    return [reqs[j] for j in traffic.sample(ctx.seed, len(reqs), k, must=longest)]


def reference_logits(ctx, rec, quant=None):
    """The reference's logits at every served position of a sample of the
    window's requests (``quant="fp8"``: the control), with the served
    tokens (-1 past each request's own length)."""
    picked = pick(ctx, rec, ctx.cell.limits["sample"])
    toks, served = reference_inputs(ctx, rec, picked)
    w = weights.make(ctx.extra["shapes"], ctx.seed, ctx.extra["shardings"])
    P = ctx.cell.traffic["prompt_len"]
    ref = reference.logits(w, ctx.cell.model, toks, P - 1, served.shape[1])
    ctl = None if quant is None else reference.logits(
        w, ctx.cell.model, toks, P - 1, served.shape[1], quant=quant)
    print(f"[serve_batch] compared {int((served >= 0).sum())} served tokens of "
          f"{len(picked)} requests {picked} with the reference", flush=True)
    return ref, ctl, served


def check(ctx, rec):
    ref, _, served = reference_logits(ctx, rec)
    gap = float(compare.token_gaps(ref, served).max())
    return [("max_token_gap", gap, ctx.cell.limits["max_token_gap"]["limit"])]
