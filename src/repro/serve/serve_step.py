"""Serving: jitted prefill and single-token decode steps.

``decode`` is the `serve_step` the decode_32k / long_500k dry-run cells
lower: one new token against a KV cache (or recurrent state) of the given
context length.  Sampling is greedy with a vocab-shard-parallel argmax.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.core import comms, compat
from repro.core import policy as policy_lib
from repro.models import layers, transformer
from repro.models.model import Model
from repro.models.params import MeshInfo
from repro.serve import kv_cache, paged_kv


def greedy_token(logits, cfg, mi: MeshInfo):
    """logits [B, 1, V_loc] vocab-sharded -> [B] int32 global argmax.

    Vocab shards over the joint (possibly node-factored) model axes."""
    v_loc = logits.shape[-1]
    lo = compat.axis_index(mi.tp_axes) * v_loc
    col = lo + jnp.arange(v_loc)
    logits = jnp.where(col < cfg.vocab_size, logits[:, 0], -jnp.inf)
    val = jnp.max(logits, axis=-1)                       # [B]
    idx = lo + jnp.argmax(logits, axis=-1).astype(jnp.int32)
    gmax = comms.pmax(val, mi.tp_axes)
    cand = jnp.where(val >= gmax, idx, jnp.int32(2**31 - 1))
    return -comms.pmax(-cand, mi.tp_axes)                # pmin of candidates


class Server:
    def __init__(self, model: Model, mesh, scheme="baseline",
                 seq_axes=("model",), ring_bidir: bool = False,
                 ring_chunks: int = 1):
        self.model = model
        self.mesh = mesh
        # compile the policy against this mesh once; prefill/decode bind
        # the resulting plan (scheme names go through the rule adapter)
        self.plan = policy_lib.compile_plan(scheme, model.mi)
        # resolve the logical "model" entry to the joint axis (AxisPair on
        # a tp-node-factored mesh) so decode combines span the full tp ways
        self.seq_axes = tuple(model.mi.tp_axes if ax == "model" else ax
                              for ax in seq_axes)
        self.ring_bidir = ring_bidir
        self.ring_chunks = ring_chunks
        self._build()

    # ------------------------------------------------------------------
    def _build(self):
        model, mi, cfg = self.model, self.model.mi, self.model.cfg
        pspecs = model.specs()

        def prefill_fn(params, batch):
            with policy_lib.use_plan(self.plan), \
                    comms.ring_options(self.ring_bidir, self.ring_chunks):
                logits, caches, _ = model.forward(params, batch,
                                                  phase="prefill")
                tok = greedy_token(logits[:, -1:], cfg, mi)
            return tok, caches

        def decode_fn(params, token, caches, index):
            with policy_lib.use_plan(self.plan), comms.vma_mode(False), \
                    comms.ring_options(self.ring_bidir, self.ring_chunks):
                x = layers.embed(params["embed"], token, cfg, mi, sp=False)
                pos3 = None
                if cfg.mrope:
                    B = token.shape[0]
                    pos3 = jnp.broadcast_to(index.astype(jnp.int32),
                                            (B, 1, 3))
                new_caches = []
                for i, g in enumerate(cfg.layer_groups):
                    if g.kind == "enc_attn":
                        new_caches.append(None)
                        continue
                    x, nc = transformer.decode_group(
                        params["groups"][i], x, caches[i], index, g, cfg, mi,
                        model.mode, self.seq_axes,
                        shared=params.get("shared"), pos3=pos3)
                    new_caches.append(nc)
                x = layers.norm(params["final_norm"], x, cfg, mi)
                logits = layers.lm_head_logits(params, x, cfg, mi, sp=False)
                tok = greedy_token(logits, cfg, mi)
            return tok, new_caches

        self.decode_inner = decode_fn
        self.prefill_inner = prefill_fn

    # ------------------------------------------------------------------
    def decode_step(self, B: int, s_max: int, s_enc: int = 0):
        """Jitted serve_step: (params, token [B,1], caches, index) ->
        (next_token [B], caches)."""
        model, mi, cfg = self.model, self.model.mi, self.model.cfg
        structs, cspecs = kv_cache.cache_structs(
            cfg, mi, B, s_max, self.seq_axes, s_enc=s_enc)
        tok_spec = P(None if (B == 1 or "data" in self.seq_axes)
                     else mi.batch_axes, None)
        out_tok_spec = P(tok_spec[0])
        fn = jax.shard_map(
            self.decode_inner, mesh=self.mesh,
            in_specs=(model.specs(), tok_spec, cspecs, P()),
            out_specs=(out_tok_spec, cspecs), check_vma=False)
        return jax.jit(fn, donate_argnums=(2,)), structs, cspecs

    def prefill_step(self, bspecs, B: int):
        model, mi, cfg = self.model, self.model.mi, self.model.cfg
        cache_specs = kv_cache.prefill_cache_specs(cfg, mi, B)
        tok_spec = P(mi.batch_axes if B > 1 else None)
        fn = jax.shard_map(
            self.prefill_inner, mesh=self.mesh,
            in_specs=(model.specs(), bspecs),
            out_specs=(tok_spec, cache_specs), check_vma=False)
        return jax.jit(fn)


class PagedServer:
    """Continuous-batching decode over a paged (optionally quantized-at-rest)
    KV pool.

    One jitted step advances a FIXED set of decode slots: per-slot token,
    position, block table, and active mask come from the host scheduler
    (:mod:`repro.serve.scheduler`), so admitting/evicting requests swaps
    host arrays only — shapes never change and nothing recompiles.  With
    ``kv_codec="bq8"`` etc. the pool stores bq wire planes and every
    attention read gathers + dequantizes them through the Pallas bq
    kernels; ``"none"`` keeps the pool in model dtype (bit-exact vs the
    dense :class:`Server`).
    """

    def __init__(self, model: Model, mesh, scheme="baseline",
                 kv_codec: str = "none",
                 block_tokens: int = paged_kv.DEFAULT_BLOCK_TOKENS,
                 ring_bidir: bool = False, ring_chunks: int = 1):
        self.model = model
        self.mesh = mesh
        self.plan = policy_lib.compile_plan(scheme, model.mi)
        self.kv_codec = kv_codec
        self.bits = paged_kv.storage_bits(kv_codec)
        self.block_tokens = block_tokens
        self.ring_bidir = ring_bidir
        self.ring_chunks = ring_chunks
        self._build()

    # ------------------------------------------------------------------
    def _build(self):
        model, mi, cfg = self.model, self.model.mi, self.model.cfg

        def decode_fn(params, token, pool, tables, pos, active):
            with policy_lib.use_plan(self.plan), comms.vma_mode(False), \
                    comms.ring_options(self.ring_bidir, self.ring_chunks):
                x = layers.embed(params["embed"], token, cfg, mi, sp=False)
                pos3 = None
                if cfg.mrope:
                    pos3 = jnp.broadcast_to(
                        pos.astype(jnp.int32)[:, None, None],
                        (token.shape[0], 1, 3))
                new_pool = []
                for i, g in enumerate(cfg.layer_groups):
                    x, npl = transformer.decode_group_paged(
                        params["groups"][i], x, pool[i], tables, pos,
                        active, g, cfg, mi, bits=self.bits,
                        block_tokens=self.block_tokens,
                        shared=params.get("shared"), pos3=pos3)
                    new_pool.append(npl)
                x = layers.norm(params["final_norm"], x, cfg, mi)
                logits = layers.lm_head_logits(params, x, cfg, mi, sp=False)
                tok = greedy_token(logits, cfg, mi)
            return tok, new_pool

        self.decode_inner = decode_fn

    # ------------------------------------------------------------------
    def decode_step(self, n_slots: int, n_blocks: int, max_blocks: int):
        """Jitted serve_step: (params, token [N,1], pool, tables [N,mb],
        pos [N], active [N]) -> (next_token [N], pool).

        ``n_blocks`` is the GLOBAL pool size (must divide by dp — each
        data shard owns ``n_blocks/dp`` blocks and its slots carry LOCAL
        block ids); ``max_blocks`` bounds any single request's context at
        ``max_blocks * block_tokens`` tokens."""
        model, mi, cfg = self.model, self.model.mi, self.model.cfg
        if n_slots % mi.batch_ways or n_blocks % mi.batch_ways:
            raise ValueError(
                f"n_slots ({n_slots}) and n_blocks ({n_blocks}) must divide "
                f"by the data ways ({mi.batch_ways})")
        structs, pspecs = paged_kv.pool_structs(
            cfg, mi, n_blocks, self.block_tokens, self.kv_codec)
        bs = mi.batch_axes if mi.dp > 1 else None
        fn = jax.shard_map(
            self.decode_inner, mesh=self.mesh,
            in_specs=(model.specs(), P(bs, None), pspecs, P(bs, None),
                      P(bs), P(bs)),
            out_specs=(P(bs), pspecs), check_vma=False)
        return jax.jit(fn, donate_argnums=(2,)), structs, pspecs
