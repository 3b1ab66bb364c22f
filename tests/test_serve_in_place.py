"""The served decode step updates its KV cache in place: compiled as
`greedy_decode` compiles it, the cache is donated and aliased to the
output, and no operation of the layer scan's own plumbing (under
`layers`, outside `block`) moves a whole layer of the cache or the
stacked buffer; served with donation, it gives the tokens of the
undonated step."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.lib import attribution
from repro.configs import get_arch
from repro.models import build_model
from repro.train.serve_loop import greedy_decode, serving_jit

B, P, S_MAX, STEPS = 2, 8, 16, 5
INSTRUCTION = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*\w+\[([\d,]*)\][^ ]*\s+([\w\-]+)\(")
PARAMETER = re.compile(r"^\s*%?[\w.\-]+\s*=.*\bparameter\((\d+)\).*op_name=\"cache\[")
# instructions that name a buffer without moving it
NO_DATA = {"get-tuple-element", "tuple", "parameter", "bitcast", "while"}


@pytest.fixture(scope="module")
def served():
    model = build_model(get_arch("stablelm-3b").reduced())
    params = model.init(jax.random.PRNGKey(0))
    return model, params


def test_decode_step_donates_and_moves_no_whole_cache(served):
    model, params = served
    shapes = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params)
    # f32, as the CPU serves it: the CPU backend computes bf16 updates in
    # f32, converting the whole buffer around them
    cache = jax.eval_shape(lambda: model.init_cache(batch=B, s_max=S_MAX,
                                                    dtype=jnp.float32))
    text = serving_jit(model.decode_step).lower(
        shapes, jax.ShapeDtypeStruct((B, 1), jnp.int32), cache,
        jax.ShapeDtypeStruct((), jnp.int32)).compile().as_text()

    header = text.splitlines()[0]
    aliased = {int(n) for n in re.findall(r"\((\d+), \{\}, (?:may|must)-alias\)", header)}
    cache_params = {int(m.group(1)) for m in map(PARAMETER.match, text.splitlines()) if m}
    assert len(cache_params) == len(jax.tree.leaves(cache))
    assert cache_params <= aliased

    whole = set()
    for leaf in jax.tree.leaves(cache):
        whole |= {leaf.shape, leaf.shape[1:], (1, *leaf.shape[1:])}
    scopes = attribution.op_scopes(text)
    plumbing = []
    for line in text.splitlines():
        m = INSTRUCTION.match(line)
        if m is None or m.group(3) in NO_DATA:
            continue
        name, dims = m.group(1), m.group(2)
        shape = tuple(int(d) for d in dims.split(",")) if dims else ()
        if shape in whole and attribution.region(scopes.get(name, "")) == "scan":
            plumbing.append(line.strip()[:160])
    assert not plumbing, plumbing


def test_donated_serving_gives_the_undonated_tokens(served):
    model, params = served
    ids = jnp.arange(B * P, dtype=jnp.int32).reshape(B, P) % model.cfg.vocab_size
    got = greedy_decode(model, params, {"tokens": ids}, s_max=S_MAX, steps=STEPS,
                        cache_dtype=jnp.bfloat16)

    # the same loop on the host, nothing donated
    cache = model.init_cache(batch=B, s_max=S_MAX, dtype=jnp.bfloat16)
    logits, cache, n = jax.jit(model.prefill)(params, {"tokens": ids}, cache)
    n = jnp.asarray(n, jnp.int32)
    step = jax.jit(model.decode_step)
    want = []
    tok = jnp.argmax(logits[:, -1], axis=-1)[:, None]
    for _ in range(STEPS):
        want.append(tok)
        logits, cache, n = step(params, tok, cache, n)
        tok = jnp.argmax(logits[:, -1], axis=-1)[:, None]
    np.testing.assert_array_equal(np.asarray(got.tokens),
                                  np.asarray(jnp.concatenate(want, axis=1)))

    # a donated cache is consumed: greedy_decode, which finished, read none
    before = jax.tree.leaves(cache)
    serving_jit(model.decode_step)(params, tok, cache, n)
    assert all(a.is_deleted() for a in before)


def test_head_sharded_decode_kernel_serves_the_reference_tokens(monkeypatch):
    """The decode kernel (interpret mode) inside the tensor-parallel path,
    one shard_map program per head shard of the cache, on four host
    devices: the tokens of the unsharded reference path."""
    import dataclasses
    import functools

    from jax.sharding import Mesh

    import repro.models.attention as attention
    from repro.kernels.decode_attention import decode_attention

    cfg = dataclasses.replace(get_arch("qwen3-14b").reduced(), n_heads=8, n_kv_heads=4)
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    ids = jnp.arange(B * P, dtype=jnp.int32).reshape(B, P) % cfg.vocab_size
    want = greedy_decode(build_model(cfg), params, {"tokens": ids}, s_max=S_MAX,
                         steps=STEPS).tokens
    mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(1, 4), ("data", "model"))
    model = build_model(cfg, mesh=mesh)
    cache = model.init_cache(batch=B, s_max=S_MAX, dtype=jnp.float32)
    assert cache["main"].k.sharding.spec[2] == "model"
    monkeypatch.setattr(attention, "decode_attention",
                        functools.partial(decode_attention, interpret=True))
    got = greedy_decode(model, params, {"tokens": ids}, s_max=S_MAX, steps=STEPS).tokens
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
