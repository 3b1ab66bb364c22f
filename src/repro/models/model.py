"""Model builder: assembles any assigned architecture from its ArchConfig.

One ``Model`` object exposes the full lifecycle:
    init / param_axes           — declarative specs (spec.py)
    loss(params, batch)         — training forward + CE (+ MoE aux)
    prefill / decode_step       — serving with per-family caches
Layer stacks are ``lax.scan``-ed (stacked params) so 80-layer models lower
in O(1 layer) — required for the 512-device dry-run compiles.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import blocks as B
from repro.models.attention import (
    batch_axes, init_kv_cache, init_mla_cache, pin_cache,
)
from repro.models.common import (
    cross_entropy,
    embed_apply,
    embed_specs,
    lm_head_apply,
    rms_norm,
    rms_norm_spec,
)
from repro.models.spec import Spec, init_params, param_axes, stack_specs
from repro.models.ssm import init_mamba_cache
from repro.models.xlstm import init_mlstm_cache, init_slstm_cache

MOE_AUX_COEF = 1e-3


@dataclass
class Model:
    cfg: ArchConfig
    mesh: Any = None                 # set by the launcher for EP MoE
    moe_mode: str = "auto"           # auto | capacity | ep
    moe_capacity_factor: float = 1.25
    remat: str = "none"              # none | full | dots

    # ------------------------------------------------------------- specs
    def specs(self) -> Dict[str, Any]:
        cfg = self.cfg
        s: Dict[str, Any] = {"embed": embed_specs(cfg.vocab_size, cfg.d_model,
                                                  cfg.tie_embeddings),
                             "final_norm": rms_norm_spec(cfg.d_model)}
        fam = cfg.family
        if fam in ("dense", "audio", "vlm"):
            s["layers"] = stack_specs(
                B.attn_block_specs(cfg, cfg.d_ff, moe=False), cfg.n_layers
            )
        elif fam == "moe":
            s["dense_layers"] = stack_specs(
                B.attn_block_specs(cfg, cfg.dense_d_ff or cfg.d_ff, moe=False),
                cfg.first_dense_layers,
            )
            s["layers"] = stack_specs(
                B.attn_block_specs(cfg, cfg.d_ff, moe=True),
                cfg.n_layers - cfg.first_dense_layers,
            )
        elif fam == "hybrid":
            s["layers"] = stack_specs(
                B.zamba_layer_specs(cfg), cfg.n_layers
            )
            # the shared blocks and the uses' own weights as lists, not
            # stacks: a use reads its block whole, never a slice of a stack
            s["shared"] = [B.zamba_shared_specs(cfg)
                           for _ in range(cfg.num_mem_blocks)]
            s["hybrid"] = [B.zamba_use_specs(cfg) for _ in cfg.hybrid_layer_ids]
        elif fam == "ssm":
            n_groups = cfg.n_layers // cfg.slstm_every
            s["layers"] = stack_specs(
                B.xlstm_group_specs(cfg), n_groups
            )
        else:
            raise ValueError(fam)
        return s

    def init(self, key: jax.Array, dtype=jnp.float32):
        return init_params(self.specs(), key, dtype)

    def param_axes(self):
        return param_axes(self.specs())

    # ------------------------------------------------------- embeddings
    def _embed_inputs(self, params, batch):
        cfg = self.cfg
        if cfg.frontend == "audio_frames":
            return batch["frames"]  # precomputed (B, T, D) — stub frontend
        x = embed_apply(params["embed"], batch["tokens"])
        if cfg.frontend == "vision_patches":
            x = jnp.concatenate([batch["patches"], x], axis=1)
        return x

    # ------------------------------------------------------------ layers
    def _run_layers(self, params, x, positions, cache=None, cache_len=None):
        cfg = self.cfg
        fam = cfg.family
        aux_total = jnp.zeros((), jnp.float32)

        if fam in ("dense", "audio", "vlm", "moe"):
            cache_d = None
            if fam == "moe" and cfg.first_dense_layers:
                x, cache_d, _ = self._scan_attn(
                    params["dense_layers"], x, positions, moe=False,
                    cache=None if cache is None else cache["dense"],
                    cache_len=cache_len, layer_offset=0,
                )
            x, cache_m, aux = self._scan_attn(
                params["layers"], x, positions, moe=(fam == "moe"),
                cache=None if cache is None else cache["main"],
                cache_len=cache_len, layer_offset=cfg.first_dense_layers,
            )
            aux_total += aux
            new_cache = (
                None if cache is None
                else {"dense": cache_d, "main": cache_m}
            )
        elif fam == "hybrid":
            x, new_cache = self._run_zamba(
                params, x, positions, cache, cache_len
            )
        else:  # ssm / xlstm
            x, new_cache = self._scan_xlstm(params, x, cache)

        x = rms_norm(params["final_norm"], x, cfg.norm_eps)
        return x, new_cache, aux_total

    def _scan_attn(self, stack, x, positions, *, moe, cache, cache_len,
                   layer_offset):
        """The layer stack ``stack`` scanned over ``x``.  The stacked cache
        rides in the carry, not in the scanned inputs and outputs: each
        layer writes its new tokens into its own row (``i - layer_offset``)
        and its attention reads that row, so a step moves no layer's cache
        but through the attention, and the buffer is updated in place."""
        cfg = self.cfg

        @jax.named_scope("block")
        def body(carry, p):
            x, i, c = carry
            global_layer = None
            if cfg.sliding_window and cfg.local_global_ratio:
                # gemma3: every (r+1)-th layer global, the others windowed
                r = cfg.local_global_ratio
                global_layer = (i % (r + 1)) == r
            y, c, aux = B.attn_block_apply(
                p, x, cfg, positions, moe=moe, window=cfg.sliding_window,
                global_layer=global_layer, cache=c, cache_len=cache_len,
                layer=i - layer_offset, mesh=self.mesh,
                moe_mode=self.moe_mode,
                moe_capacity_factor=self.moe_capacity_factor,
            )
            return (y, i + 1, c), aux

        if self.remat == "full":
            body = jax.checkpoint(body)
        elif self.remat == "dots":
            # save matmul outputs: the backward skips recomputing the TP
            # GEMMs *and their psum all-reduces* (§Perf train iteration)
            body = jax.checkpoint(
                body,
                policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
            )
        # the same sharding in and out of the step, so the next step's
        # compiled program takes the cache this one returns
        pin = functools.partial(jax.tree.map, lambda c: pin_cache(c, self.mesh))
        with jax.named_scope("layers"):
            (x, _, new_cache), auxs = jax.lax.scan(
                body, (x, layer_offset, pin(cache)), stack
            )
        return x, pin(new_cache), auxs.sum()

    def _run_zamba(self, params, x, positions, cache, cache_len):
        """zamba2's layers in their published order.  The Mamba2 layers run
        as `lax.scan`s, one over each stretch between two hybrid layers;
        before each hybrid layer, in Python, a shared block (the uses take
        the ``num_mem_blocks`` blocks in turn) reads [stream ‖ embedding]
        and its use's ``linear`` output is added to that layer's input.
        The stacked Mamba2 cache rides in each scan's carry and the KV
        stack of the uses beside it: every layer writes its own row, so
        both are updated in place."""
        cfg = self.cfg
        emb = x
        mamba = None if cache is None else cache["mamba"]
        kv = None if cache is None else cache["kv"]
        stack = params["layers"]

        # one body for every stretch, so that it is traced and lowered once
        @jax.named_scope("block")
        def body(carry, _):
            x, add, i, c = carry
            p = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
                a, i, keepdims=False), stack)
            y, c = B.zamba_layer_apply(p, x, add, cfg, cache=c, layer=i,
                                       mesh=self.mesh)
            return (y, jnp.zeros_like(add), i + 1, c), None

        if self.remat == "full":
            body = jax.checkpoint(body)

        def run(x, add, start, stop, mamba):
            (x, _, _, mamba), _ = jax.lax.scan(
                body, (x, add, jnp.asarray(start, jnp.int32), mamba), None,
                length=stop - start)
            return x, mamba

        # the uses as calls of one jitted function, traced and lowered once
        # (its kernels too); XLA inlines the calls
        shared = jax.jit(functools.partial(B.zamba_shared_apply, cfg=cfg, mesh=self.mesh))
        bounds = [*cfg.hybrid_layer_ids, cfg.n_layers]
        with jax.named_scope("layers"):
            x, mamba = run(x, jnp.zeros_like(x), 0, bounds[0], mamba)
            for k, (start, stop) in enumerate(zip(bounds, bounds[1:])):
                with jax.named_scope("block"):
                    add, kv = shared(
                        params["shared"][k % cfg.num_mem_blocks],
                        params["hybrid"][k], x, emb, positions=positions, cache=kv,
                        cache_len=cache_len, use_idx=jnp.asarray(k, jnp.int32))
                x, mamba = run(x, add, start, stop, mamba)
        return x, None if cache is None else {"mamba": mamba, "kv": kv}

    def _scan_xlstm(self, params, x, cache):
        cfg = self.cfg

        @jax.named_scope("block")
        def body(x, inp):
            p, c = inp
            y, new_c = B.xlstm_group_apply(p, x, cfg, cache=c)
            return y, new_c

        if self.remat == "full":
            body = jax.checkpoint(body)
        with jax.named_scope("layers"):
            x, new_cache = jax.lax.scan(body, x, (params["layers"], cache))
        return x, new_cache

    # ----------------------------------------------------------- training
    def forward(self, params, batch):
        x = self._embed_inputs(params, batch)
        Bsz, T = x.shape[:2]
        positions = jnp.broadcast_to(jnp.arange(T)[None], (Bsz, T))
        x, _, aux = self._run_layers(params, x, positions)
        with jax.named_scope("lm_head"):
            logits = lm_head_apply(params["embed"], x)
        return logits, aux

    def loss(self, params, batch):
        logits, aux = self.forward(params, batch)
        labels = batch["labels"]
        if self.cfg.frontend == "vision_patches":
            # patches are unsupervised context: align labels to text tail.
            logits = logits[:, -labels.shape[1]:]
        ce = cross_entropy(logits, labels)
        total = ce + MOE_AUX_COEF * aux
        return total, {"ce": ce, "aux": aux}

    # ------------------------------------------------------------ serving
    def init_cache(self, batch: int, s_max: int, dtype=jnp.bfloat16):
        """A zero cache for ``batch`` sequences of up to ``s_max`` positions.
        On the model's mesh it is made in place, in `cache_pspecs`' layout:
        made on one device, it would be copied to every device of the mesh
        on the way into the prefill."""
        make = functools.partial(self._zero_cache, batch, s_max, dtype)
        if self.mesh is None:
            return make()
        from jax.sharding import NamedSharding, PartitionSpec

        pspecs = self.cache_pspecs(self.mesh, jax.eval_shape(make))
        return jax.jit(make, out_shardings=jax.tree.map(
            lambda p: NamedSharding(self.mesh, p), pspecs,
            is_leaf=lambda x: isinstance(x, PartitionSpec)))()

    def _zero_cache(self, batch: int, s_max: int, dtype):
        cfg = self.cfg
        fam = cfg.family

        def kv(n):
            mk = (
                init_mla_cache if cfg.attn_type == "mla" else init_kv_cache
            )
            one = mk(cfg, batch, s_max, dtype)
            return jax.tree.map(
                lambda a: jnp.broadcast_to(a[None], (n, *a.shape)).copy()
                if n else a,
                one,
            )

        if fam in ("dense", "audio", "vlm"):
            return {"dense": None, "main": kv(cfg.n_layers)}
        if fam == "moe":
            return {
                "dense": kv(cfg.first_dense_layers),
                "main": kv(cfg.n_layers - cfg.first_dense_layers),
            }
        if fam == "hybrid":
            # Mamba2 state for every layer; K/V only for the shared blocks' uses
            return {
                "mamba": init_mamba_cache(cfg, batch, dtype, cfg.n_layers),
                "kv": kv(len(cfg.hybrid_layer_ids)),
            }
        if fam == "ssm":
            n_groups = cfg.n_layers // cfg.slstm_every
            k = cfg.slstm_every

            def stack(tree, *ns):
                for n in reversed(ns):
                    tree = jax.tree.map(
                        lambda a: jnp.broadcast_to(a[None], (n, *a.shape)).copy(),
                        tree,
                    )
                return tree

            return {
                "mlstm": stack(init_mlstm_cache(cfg, batch, dtype), n_groups, k - 1),
                "slstm": stack(init_slstm_cache(cfg, batch, dtype), n_groups),
            }
        raise ValueError(fam)

    @jax.named_scope("prefill")
    def prefill(self, params, batch, cache):
        """Feed a prompt; returns (last-token logits, cache, new length)."""
        x = self._embed_inputs(params, batch)
        Bsz, T = x.shape[:2]
        positions = jnp.broadcast_to(jnp.arange(T)[None], (Bsz, T))
        cache_len = jnp.zeros((), jnp.int32)
        x, new_cache, _ = self._run_layers(
            params, x, positions, cache=self._wrap_cache(cache),
            cache_len=cache_len,
        )
        with jax.named_scope("lm_head"):
            logits = lm_head_apply(params["embed"], x[:, -1:])
        return logits, self._unwrap_cache(new_cache, cache), T

    @jax.named_scope("decode_step")
    def decode_step(self, params, tokens, cache, cache_len):
        """One-token step.  tokens (B, 1) (or frames (B,1,D) for audio)."""
        cfg = self.cfg
        if cfg.frontend == "audio_frames":
            x = tokens  # (B, 1, D) frame embedding
        else:
            x = embed_apply(params["embed"], tokens)
        Bsz = x.shape[0]
        positions = jnp.broadcast_to(cache_len[None, None], (Bsz, 1))
        x, new_cache, _ = self._run_layers(
            params, x, positions, cache=self._wrap_cache(cache),
            cache_len=cache_len,
        )
        with jax.named_scope("lm_head"):
            logits = lm_head_apply(params["embed"], x)
        return logits, self._unwrap_cache(new_cache, cache), cache_len + 1

    # ---------------------------------------------------- cache shardings
    def cache_pspecs(self, mesh, cache):
        """PartitionSpecs for ``cache`` (an init_cache tree or its
        eval_shape): batch over DP axes where divisible, head/channel dims
        over 'model' where divisible."""
        from jax.sharding import PartitionSpec as P

        cfg = self.cfg
        msize = mesh.shape.get("model", 1)
        dp_for = functools.partial(batch_axes, mesh)

        def m_for(d):
            return "model" if (msize > 1 and d % msize == 0) else None

        def kv_spec(tree, lead):
            # KVCache (L,B,H,hd,S) | MLACache ckv (L,B,S,r), krope (L,B,S,dr)
            def one(x):
                sh = x.shape
                if len(sh) == 5:   # k/v
                    return P(*lead, dp_for(sh[1]), m_for(sh[2]), None, None)
                return P(*lead, dp_for(sh[1]), None, None)
            return jax.tree.map(one, tree)

        fam = cfg.family
        c = cache
        if fam in ("dense", "audio", "vlm"):
            return {"dense": None, "main": kv_spec(c["main"], (None,))}
        if fam == "moe":
            return {"dense": kv_spec(c["dense"], (None,)),
                    "main": kv_spec(c["main"], (None,))}
        if fam == "hybrid":

            def mamba_one(x):
                sh = x.shape
                if len(sh) == 5:   # state (L,B,rows,N,lanes), mamba_scan.layout
                    return P(None, dp_for(sh[1]), m_for(sh[2]), None, None)
                return P(None, None, dp_for(sh[2]), m_for(sh[3]))  # conv (L,k-1,B,C)
            return {"mamba": jax.tree.map(mamba_one, c["mamba"]),
                    "kv": kv_spec(c["kv"], (None,))}
        # ssm / xlstm
        def ml_one(x):
            sh = x.shape  # (G, k-1, B, ...) trees
            rest = [None] * (len(sh) - 3)
            if len(sh) >= 5:  # C/n: (G,k-1,B,H,N/1,P?) → shard H if divisible
                rest[0] = m_for(sh[3])
            return P(None, None, dp_for(sh[2]), *rest)

        def sl_one(x):
            sh = x.shape  # (G, B, H, P)
            return P(None, dp_for(sh[1]), m_for(sh[2]), None)

        return {"mlstm": jax.tree.map(ml_one, c["mlstm"]),
                "slstm": jax.tree.map(sl_one, c["slstm"])}

    def cache_nbytes(self, cache) -> Dict[str, int]:
        """Bytes of each kind of state in ``cache`` (an `init_cache` tree),
        read from its arrays: ``kv`` (attention K/V or latents) and ``ssm``
        (recurrent state and conv inputs)."""
        def nbytes(tree):
            return sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(tree))

        fam = self.cfg.family
        if fam == "hybrid":
            return {"ssm": nbytes(cache["mamba"]), "kv": nbytes(cache["kv"])}
        return {"ssm" if fam == "ssm" else "kv": nbytes(cache)}

    # dense/moe caches are dicts keyed like the scan stacks already
    def _wrap_cache(self, cache):
        if self.cfg.family in ("dense", "audio", "vlm"):
            return {"dense": None, "main": cache["main"]}
        return cache

    def _unwrap_cache(self, new_cache, old_cache):
        return new_cache


def build_model(cfg: ArchConfig, mesh=None, **kw) -> Model:
    return Model(cfg=cfg, mesh=mesh, **kw)
