"""The program's zamba2 against the plain reference, the hybrid driver end
to end at a reduced size (zamba2-7b's reduced preset on the CPU), the
faults its comparison has to catch, and the shape count its metrics
read."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import run as R
from bench.lib import flops_hybrid
from bench.tests.conftest import DATA, E2E, SERVE, measure
from repro.configs import get_arch, list_archs
from repro.configs.base import register

TINY_ARCH = "zamba2-7b-smoke"      # the program's reduced zamba2-7b, `tiny-hybrid.json`
if TINY_ARCH not in list_archs():
    register(get_arch("zamba2-7b").reduced())
TRAFFIC = {**SERVE, "driver": "serve_batch_hybrid"}
# `max_token_gap` of the program on this cell: 0.0 on the seeds tried (up
# to 0.03 with longer prompts and outputs); each fault below but the bf16
# state reads 0.6 or more.  A state kept in bf16 reads as the program does
# (0.026-0.033 against 0.018-0.029 over 200-token outputs), so
# `cache_bytes_off` catches it.
LIMIT = 0.06


def hybrid_cell() -> R.Cell:
    return R.Cell("tiny-hybrid", {"name": "tiny-hybrid", "config": "tiny-hybrid",
                                  "traffic": "tiny", "chips": 1},
                  R.load_json(DATA / "tiny-hybrid.json"), dict(TRAFFIC),
                  {"sample": 3, "max_token_gap": {"limit": LIMIT},
                   "cache_bytes_off": {"limit": 0.0}},
                  {"end_to_end": E2E, "per_layer": []})


@pytest.fixture(scope="module")
def tiny():
    """The program's tiny preset (two shared blocks used in turn, two B/C
    groups, a concat input, uses at the uneven layers 1, 4 and 5 of 7),
    its f32 weights, tokens, and the reference's logits over them."""
    from bench.lib import reference_zamba2
    from repro.models import build_model

    model = build_model(get_arch(TINY_ARCH))
    params = model.init(jax.random.PRNGKey(0), jnp.float32)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0, model.cfg.vocab_size)
    m = R.load_json(DATA / "tiny-hybrid.json")["run"]
    return model, params, tokens, reference_zamba2.logits(params, m, tokens, 0, tokens.shape[1])


# Program and reference run in f32 on the same weights, so they differ only
# by the order of summation: the chunked scan against the reference's one
# position at a time, a blocked softmax against a plain one.  Those agree
# to about 1e-4 of logits whose standard deviation is about 1; the
# tolerance is 20 times that, and a bf16 rounding anywhere in the stream
# (3e-3 relative) or any fault of the structure moves logits by 1e-2 or
# more.
TOL = 2e-3


def test_forward_matches_reference(tiny):
    model, params, tokens, ref = tiny
    logits, _ = model.forward(params, {"tokens": tokens})
    assert float(jnp.std(ref)) > 0.3
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref), atol=TOL, rtol=0)


def test_prefill_then_decode_through_the_cache_matches_reference(tiny):
    model, params, tokens, ref = tiny
    P = 7
    cache = model.init_cache(batch=2, s_max=16, dtype=jnp.float32)
    logits, cache, n = jax.jit(model.prefill)(params, {"tokens": tokens[:, :P]}, cache)
    np.testing.assert_allclose(np.asarray(logits[:, 0]), np.asarray(ref[:, P - 1]),
                               atol=TOL, rtol=0)
    step = jax.jit(model.decode_step)
    n = jnp.asarray(n, jnp.int32)
    for t in range(P, tokens.shape[1]):
        logits, cache, n = step(params, tokens[:, t:t + 1], cache, n)
        np.testing.assert_allclose(np.asarray(logits[:, 0]), np.asarray(ref[:, t]),
                                   atol=TOL, rtol=0, err_msg=f"position {t}")


def test_serve_hybrid_is_correct():
    res = measure(hybrid_cell())
    assert res["correct"], res["checks"]
    assert res["checks"]["cache_bytes_off"]["value"] == 0.0
    assert set(res["metrics"]) == {"tok_s", "tpot_ms", "setup_s"}
    assert res["attempted"] >= 4 and res["failed"] == 0


def _drop_embedding(monkeypatch):
    import repro.models.blocks as blocks

    shared = blocks.zamba_shared_apply
    monkeypatch.setattr(blocks, "zamba_shared_apply", lambda p, use, x, emb, *a, **k:
                        shared(p, use, x, jnp.zeros_like(emb), *a, **k))


def _skip_adapter(monkeypatch):
    import repro.models.blocks as blocks

    mlp = blocks.gated_mlp_apply
    monkeypatch.setattr(blocks, "gated_mlp_apply", lambda p, x, adapter: mlp(p, x, None))


def _swap_blocks(monkeypatch):
    from repro.models.model import Model

    run = Model._run_zamba
    monkeypatch.setattr(Model, "_run_zamba", lambda self, params, *a: run(
        self, {**params, "shared": params["shared"][::-1]}, *a))


def _state_in_bf16(monkeypatch):
    import repro.models.model as model
    from repro.models.ssm import MambaCache

    init = model.init_mamba_cache
    monkeypatch.setattr(model, "init_mamba_cache", lambda *a: MambaCache(
        init(*a).conv, init(*a).state.astype(jnp.bfloat16)))


def _one_group(monkeypatch):
    import repro.models.ssm as ssm

    first = lambda t: jnp.broadcast_to(t[..., :1, :], t.shape)   # group 0 for all heads
    step, scan = ssm.ssm_step, ssm.mamba_chunk_scan
    monkeypatch.setattr(ssm, "ssm_step", lambda s, x, dt, A, Bm, Cm, D:
                        step(s, x, dt, A, first(Bm), first(Cm), D))
    monkeypatch.setattr(ssm, "mamba_chunk_scan", lambda x, dt, A, Bm, Cm, **k:
                        scan(x, dt, A, first(Bm), first(Cm), **k))


@pytest.mark.parametrize("fault", [_drop_embedding, _skip_adapter, _swap_blocks,
                                   _state_in_bf16, _one_group],
                         ids=["concat-dropped", "adapter-skipped", "blocks-swapped",
                              "state-bf16", "one-group"])
def test_fault_is_caught(monkeypatch, fault):
    fault(monkeypatch)
    res = measure(hybrid_cell())
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("arch,run", [
    (TINY_ARCH, "tiny"), ("zamba2-7b", "cell")])
def test_shape_count_is_the_served_cache(arch, run):
    """`flops_hybrid.cache_bytes` equals the bytes of the program's zero
    cache, at the tiny preset and at the cell's size."""
    from repro.models import build_model

    if run == "tiny":
        m, B, s_max = R.load_json(DATA / "tiny-hybrid.json")["run"], 4, 25
    else:
        m, B, s_max = R.load_json(R.ROOT / "bench/configs/zamba2-7b.json")["run"], 32, 769
    model = build_model(get_arch(arch))
    cache = jax.eval_shape(lambda: model.init_cache(batch=B, s_max=s_max, dtype=jnp.bfloat16))
    assert flops_hybrid.cache_bytes(m, B, s_max) == model.cache_nbytes(cache)


def test_build_refuses_what_the_program_does_not_run():
    from bench.tests.conftest import context

    drv = R.load_module("drivers", "serve_batch_hybrid")
    for key, value in (("ssm_ngroups", 1), ("num_mem_blocks", 1), ("concat_embed", False),
                       ("hybrid_layer_ids", [1, 4]), ("attn_scale", 32 ** -0.5),
                       ("chunk_size", 256)):
        cell = hybrid_cell()
        cell.config = {**cell.config, "run": {**cell.config["run"], key: value}}
        with pytest.raises(ValueError, match=key):
            drv.build(context(cell))
    cell = hybrid_cell()
    cell.config = {**cell.config, "hidden_act": "silu"}
    with pytest.raises(ValueError, match="hidden_act"):
        drv.build(context(cell))


def test_decode_program_names_the_hybrid_scopes():
    """The compiled decode step carries the scopes the metric readers
    attribute device time by: `mamba`/`ssm_update` for the Mamba2 layers
    (`ssm_roofline.decode`), `shared_block` holding `attn` and `mlp`, and
    `hybrid_linear`, all inside `block` (none counted as the layer scans'
    plumbing by `scan_plumbing_share.decode`)."""
    from bench.lib import attribution
    from bench.tests.conftest import context

    cell = hybrid_cell()
    ctx = context(cell)
    model, _, shapes, shardings = R.load_module("drivers", "serve_batch_hybrid").build(ctx)
    params = jax.tree.map(lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
                          shapes, shardings)
    scopes = attribution.op_scopes(attribution.decode_program_text(
        model, params, 4, 16, 25, jnp.bfloat16)).values()
    # names rooted at the program (the reducers inside the uses' jitted
    # calls keep only the call's own scopes; no device operation is named
    # after them)
    scopes = [s for s in scopes if s.startswith("jit(decode_step)/")]
    for path in ("mamba/ssm_update", "shared_block/attn", "shared_block/mlp", "hybrid_linear"):
        named = [s for s in scopes if path in s]
        assert named, path
        assert {attribution.region(s) for s in named} == {"block"}, path
