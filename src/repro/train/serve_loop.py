"""Serving: batched prefill + decode with fixed-capacity caches.

``make_serve_fns`` returns jit-able (prefill, decode_step); the launcher
shards the cache over the mesh (heads/latent over 'model', batch over
'data').  ``decode_tokens`` drives a simple greedy loop for the examples.

When a `repro.runtime.Runtime` is passed, every decode step also routes
its QKV/FFN GEMM descriptors through the online runtime (shadow dispatch,
DESIGN.md §10.5): the dynamic logic plans and meters the step's GEMM
bundle (§6.11 fuse-vs-group included) while the jitted model does the
math.  Telemetry then reports CD/mode/plan-cache behaviour for the run.

`greedy_decode` writes `jax.profiler` spans, each with the batch size as
``batch``: ``serve.compile`` around each lower and compile,
``serve.prefill``, and one step span ``serve.decode`` per decode step
holding ``serve.submit`` (the runtime's submits), ``serve.dispatch`` (the
jitted step's enqueue), ``serve.sample`` (the argmax) and
``serve.flush`` (the runtime's flush or drain).
"""
from __future__ import annotations

import time
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.profiler import StepTraceAnnotation, TraceAnnotation

from repro.models.model import Model


class Decoded(NamedTuple):
    """What `greedy_decode` served, with its times split by phase: the
    compile of the prefill and decode programs, the prefill itself, and
    the decode loop (every step, runtime dispatch included); and the
    bytes of each kind of cache it served from (`Model.cache_nbytes`)."""

    tokens: jax.Array          # (B, steps)
    prefill_logits: jax.Array  # (B, 1, V) last-prompt-token logits
    compile_s: float
    prefill_s: float
    decode_s: float
    cache_bytes: dict          # {"kv": ...} and, for hybrids, {"ssm": ...}


def make_serve_fns(model: Model) -> Tuple[Callable, Callable]:
    def prefill(params, batch, cache):
        return model.prefill(params, batch, cache)

    def decode_step(params, tokens, cache, cache_len):
        return model.decode_step(params, tokens, cache, cache_len)

    return prefill, decode_step


def serving_jit(fn: Callable) -> Callable:
    """``fn`` (`Model.prefill` or `Model.decode_step`, the cache their
    third argument) jitted with the cache donated: the step writes its
    tokens into the caller's buffer in place, and the caller's cache is
    consumed.  Compile each program of `greedy_decode` through this."""
    return jax.jit(fn, donate_argnums=2)


def greedy_decode(
    model: Model, params, prompt_batch, *, s_max: int, steps: int,
    cache_dtype=jnp.float32, runtime: Optional[Any] = None,
    tenant: str = "default", mixed_ops: bool = False, graph: bool = False,
):
    """Greedy generation (host loop, jitted steps); returns `Decoded`.

    ``runtime``: optional `repro.runtime.Runtime`; each decode step's
    QKV/FFN GEMM descriptors are submitted to it and flushed, so the
    online dynamic logic runs against the live decode load.

    ``mixed_ops=True`` widens the shadow dispatch to the step's FULL op
    bundle — attention, MoE grouped-GEMM, and SSD scan alongside the
    GEMMs — co-scheduled as one heterogeneous concurrent group via
    `Runtime.submit` (DESIGN.md §14).

    ``graph=True`` (implies mixed ops) submits the step as a dependency
    graph (`decode_step_graph`, DESIGN.md §19) instead of a flat bundle:
    the runtime's readiness tracker orders QKV → attention → O-proj →
    FFN/MoE itself and fills each concurrency window with whatever is
    ready — concurrent requests overlap across stage boundaries."""
    B = jax.tree.leaves(prompt_batch)[0].shape[0]
    cache = model.init_cache(batch=B, s_max=s_max, dtype=cache_dtype)
    cache_bytes = model.cache_nbytes(cache)
    t0 = time.perf_counter()
    with TraceAnnotation("serve.compile", batch=B):
        prefill = serving_jit(model.prefill).lower(
            params, prompt_batch, cache).compile()
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    with TraceAnnotation("serve.prefill", batch=B):
        logits, cache, length = prefill(params, prompt_batch, cache)
        prefill_logits = jax.block_until_ready(logits)
    prefill_s = time.perf_counter() - t0
    cache_len = jnp.asarray(length, jnp.int32)
    out = []
    tok = jnp.argmax(logits[:, -1], axis=-1)[:, None]
    t0 = time.perf_counter()
    with TraceAnnotation("serve.compile", batch=B):
        decode = serving_jit(model.decode_step).lower(
            params, tok, cache, cache_len).compile()
    compile_s += time.perf_counter() - t0
    step_requests = step_bundle = step_graph = None
    if runtime is not None and graph:
        from repro.runtime import decode_step_graph
        # the dependency structure is identical every step — build the
        # template once, submit it per step; prewarm seeds GO entries
        # plus one mixed-plan signature per topological wave
        step_graph = decode_step_graph(model.cfg, B, context=s_max)
        runtime.prewarm(step_graph)
    elif runtime is not None and mixed_ops:
        from repro.runtime import decode_step_op_descs
        # the op bundle is identical every step — derive once, submit
        # per step; prewarm seeds both the GO entries and the bundle's
        # plan-cache signature
        step_bundle = decode_step_op_descs(model.cfg, B, context=s_max)
        runtime.prewarm(step_bundle)
    elif runtime is not None:
        from repro.runtime import decode_step_requests, prewarm_decode
        prewarm_decode(runtime, model.cfg, batches=[B])
        # the bundle (incl. the §6.11 fusion decision) is identical every
        # step — derive it once, submit it per step
        step_requests = decode_step_requests(runtime.ctrl, model.cfg, B)
    t0 = time.perf_counter()
    for k in range(steps):
        with StepTraceAnnotation("serve.decode", step_num=k, batch=B):
            out.append(tok)
            if runtime is not None:
                with TraceAnnotation("serve.submit", batch=B):
                    if step_graph is not None:
                        runtime.submit(step_graph, tenant=tenant)
                    elif step_bundle is not None:
                        runtime.submit(step_bundle, tenant=tenant)
                    else:
                        for req in step_requests:
                            runtime.submit(req, tenant=tenant)
            with TraceAnnotation("serve.dispatch", batch=B):
                logits, cache, cache_len = decode(params, tok, cache, cache_len)
            with TraceAnnotation("serve.sample", batch=B):
                tok = jnp.argmax(logits[:, -1], axis=-1)[:, None]
            if runtime is not None:
                with TraceAnnotation("serve.flush", batch=B):
                    if step_graph is not None:
                        # a graph spans several flushes (each completion
                        # wave releases the next), so drain the whole step
                        runtime.drain()
                    else:
                        runtime.flush(force=True)
    tokens = jax.block_until_ready(jnp.concatenate(out, axis=1))
    return Decoded(tokens, prefill_logits, compile_s, prefill_s,
                   time.perf_counter() - t0, cache_bytes)
