"""Prefill/decode disaggregation with a compressed KV handoff.

Production serving splits prefill (compute-bound, long sequences) and
decode (memory-bound, one token) onto distinct accelerator pools; the
prompt's KV cache then has to cross the pool interconnect once per
request.  That transfer is exactly the kind of bulk, loss-tolerant
traffic the paper's codecs target, so here it rides the same policy
machinery as every training collective: a first-class ``pool`` mesh axis
(prefill = rank 0, decode = rank 1), a :func:`repro.core.comms.pool_handoff`
per cache leaf under ``Site("kv", "prefill_handoff")``, and a ``kv``
policy dimension whose codec the ``--kv-codec`` flag (or any scheme's
``kv`` field) selects.  The byte ledger attributes the handoff to the
``kv`` dimension and :func:`repro.analysis.roofline.kv_handoff_seconds`
prices it — compressed handoffs move strictly fewer bytes than
uncompressed ones, with zero traffic leaking into the tp/pp dimensions.

Mechanics: the pool axis is OUTERMOST and the model never sees it —
params are replicated across pools (their specs simply don't mention
``pool``), while the batch, caches, and token streams carry a leading
pool dim of 2.  Prefill runs on the whole mesh but only pool rank 0's
batch is real; the handoff ppermutes every cache leaf ``0 -> 1`` (the
prefill pool receives zeros — it drops its KV, as a real disaggregated
cluster would); decode then runs with real state only on pool rank 1,
where the host reads the tokens back.  Bit-exactness of the served
tokens under ``kv_codec="none"`` is asserted by
``tests/multidev/serve_page_check.py``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import comms, compat
from repro.core import policy as policy_lib
from repro.launch.mesh import LOCAL_AXIS, MODEL_AXIS
from repro.models.model import Model
from repro.serve import kv_cache
from repro.serve.serve_step import Server

POOL_AXIS = "pool"
PREFILL, DECODE = 0, 1   # pool ranks


def make_disagg_mesh(dp: int, tp: int):
    """(pool=2, data, model) mesh: pool outermost so each pool is a full
    dp x tp sub-mesh and the handoff is one hop on the slowest links."""
    import math
    need = 2 * dp * tp
    devs = jax.devices()
    assert len(devs) >= need, f"need {need} devices, have {len(devs)}"
    return compat.make_mesh((2, dp, tp), (POOL_AXIS, LOCAL_AXIS, MODEL_AXIS),
                            devices=devs[:need])


def _lift_specs(specs):
    """Prepend the pool dim to a PartitionSpec pytree (P is a tree leaf)."""
    return jax.tree.map(lambda p: P(POOL_AXIS, *p), specs,
                        is_leaf=lambda x: isinstance(x, P))


class DisaggServer:
    """Two-pool serving: prefill pool -> compressed KV handoff -> decode
    pool, sharing one :class:`~repro.serve.serve_step.Server`'s inner
    prefill/decode programs."""

    def __init__(self, model: Model, mesh, scheme="baseline",
                 kv_codec: str = "none", ring_bidir: bool = False,
                 ring_chunks: int = 1):
        mi = model.mi
        if mi.pool != 2 or mi.pool_axis != POOL_AXIS:
            raise ValueError(
                "DisaggServer needs a mesh with a 2-way 'pool' axis "
                "(make_disagg_mesh)")
        self.model = model
        self.mesh = mesh
        self.kv_codec = kv_codec
        pol = policy_lib.as_policy(scheme)
        if kv_codec != "none":
            pol = pol.with_rules(policy_lib.Rule(kv_codec, dim="kv"),
                                 name=f"{pol.name}+kv:{kv_codec}")
        self.plan = policy_lib.compile_plan(pol, mi)
        # the inner prefill/decode programs never emit kv traffic, so the
        # shared Server can bind the same plan
        self.srv = Server(model, mesh, scheme=pol, ring_bidir=ring_bidir,
                          ring_chunks=ring_chunks)

    # ------------------------------------------------------------------
    # host-side staging: real data on the prefill pool, zeros elsewhere
    # ------------------------------------------------------------------
    def stage_batch(self, batch, bspecs):
        """Host batch -> device arrays [2, ...] with the real batch at
        pool rank PREFILL and zeros at DECODE."""
        def put(a, sp):
            a = np.asarray(a)
            g = np.zeros((2,) + a.shape, a.dtype)
            g[PREFILL] = a
            return jax.device_put(
                jnp.asarray(g),
                NamedSharding(self.mesh, P(POOL_AXIS, *sp)))
        return {k: put(batch[k], bspecs[k]) for k in batch}

    # ------------------------------------------------------------------
    # jitted steps (pool-lifted wrappers over the Server's inner fns)
    # ------------------------------------------------------------------
    def prefill_step(self, bspecs, B: int):
        model, mi = self.model, self.model.mi
        cache_specs = kv_cache.prefill_cache_specs(model.cfg, mi, B)
        tok_spec = P(mi.batch_axes if B > 1 else None)

        def fn(params, batch):
            sq = jax.tree.map(lambda a: a[0], batch)
            tok, caches = self.srv.prefill_inner(params, sq)
            return jax.tree.map(lambda a: a[None], (tok, caches))

        sm = jax.shard_map(
            fn, mesh=self.mesh,
            in_specs=(model.specs(), _lift_specs(bspecs)),
            out_specs=_lift_specs((tok_spec, cache_specs)),
            check_vma=False)
        return jax.jit(sm)

    def handoff_step(self, B: int, s_max: int, s_enc: int = 0):
        """Jitted KV handoff: decode-layout caches [2, ...] -> the same,
        with pool rank DECODE holding the prefill pool's KV.

        Float leaves ride :func:`comms.pool_handoff` (compressed under
        the plan's ``kv`` codec, ledgered under the ``kv`` dimension);
        integer/bool leaves (cross-attn lengths) rotate uncompressed."""
        model, mi = self.model, self.model.mi
        _, cspecs = kv_cache.cache_structs(model.cfg, mi, B, s_max,
                                           self.srv.seq_axes, s_enc=s_enc)

        def hand(a):
            if jnp.issubdtype(a.dtype, jnp.floating):
                return comms.pool_handoff(a, POOL_AXIS, src=PREFILL,
                                          dst=DECODE)
            return lax.ppermute(a, POOL_AXIS, [(PREFILL, DECODE)])

        def fn(caches):
            with policy_lib.use_plan(self.plan), comms.vma_mode(False), \
                    comms.scope_facts(phase="kv_handoff",
                                      kv_codec=self.kv_codec):
                return jax.tree.map(hand, caches)

        lifted = _lift_specs(cspecs)
        sm = jax.shard_map(fn, mesh=self.mesh, in_specs=(lifted,),
                           out_specs=lifted, check_vma=False)
        return jax.jit(sm)

    def decode_step(self, B: int, s_max: int, s_enc: int = 0):
        """Jitted decode over the pool-lifted caches; tokens are only
        meaningful at pool rank DECODE."""
        model, mi = self.model, self.model.mi
        _, cspecs = kv_cache.cache_structs(model.cfg, mi, B, s_max,
                                           self.srv.seq_axes, s_enc=s_enc)
        tok_spec = P(None if B == 1 else mi.batch_axes, None)

        def fn(params, token, caches, index):
            sq = jax.tree.map(lambda a: a[0], (token, caches))
            tok, nc = self.srv.decode_inner(params, sq[0], sq[1], index)
            return jax.tree.map(lambda a: a[None], (tok, nc))

        lifted = _lift_specs(cspecs)
        sm = jax.shard_map(
            fn, mesh=self.mesh,
            in_specs=(model.specs(), _lift_specs(tok_spec), lifted, P()),
            out_specs=(P(POOL_AXIS, tok_spec[0]), lifted), check_vma=False)
        return jax.jit(sm, donate_argnums=(2,))

    # ------------------------------------------------------------------
    def pad_prefill_caches(self, caches, B: int, s_max: int, s_enc: int = 0):
        """Host: pool-lifted prefill caches -> zero-padded decode layout."""
        model, mi = self.model, self.model.mi
        structs, cspecs = kv_cache.cache_structs(model.cfg, mi, B, s_max,
                                                 self.srv.seq_axes,
                                                 s_enc=s_enc)
        padded = []
        for st, cs, pc in zip(structs, cspecs, caches):
            if st is None:
                padded.append(None)
                continue
            new = {}
            for k, v in st.items():
                shape = (2,) + tuple(v.shape)
                if k == "xlen":
                    a = np.full(shape, s_enc, np.int32)
                else:
                    a = np.zeros(shape, v.dtype)
                    if pc is not None and k in pc:
                        s = np.asarray(pc[k])
                        a[tuple(slice(0, d) for d in s.shape)] = s
                new[k] = jax.device_put(
                    jnp.asarray(a),
                    NamedSharding(self.mesh, P(POOL_AXIS, *cs[k])))
            padded.append(new)
        return padded
