"""Compression-assisted collectives (the paper's core mechanism, TPU-native).

Every collective the framework emits goes through this module, tagged with
a :class:`Site` (or a legacy tag string): the parallelism dimension it
serves (``dp``/``zero``/``tp``/``pp``/``ep``), an optional site name for
per-tensor rules, and an optionally pinned direction/level.  The active
compiled :class:`~repro.core.policy.CommPlan` (``policy.use_plan``, else
the adapter plan of the thread-local :mod:`repro.core.schemes` scheme)
maps each site — plus the trace-time payload size — to a codec:

* identity codecs (``none``, ``mpc``) lower to stock ``jax.lax`` collectives —
  the uncompressed MVAPICH2-GDR baseline of the paper;
* ``bq*`` codecs lower to compression-assisted implementations in which the
  *wire payload is the encoded pytree*:

    - all-gather / ppermute / all-to-all: encode once -> collective on the
      int8/int16 wire -> decode;
    - reduce-scatter / all-reduce: a ring over ``lax.ppermute`` whose per-hop
      payload is encoded, with the fused ``decode->add->encode`` Pallas kernel
      as the hop body.  all-reduce = ring reduce-scatter + all-gather of the
      final *compressed* chunk — exactly the paper's compression-assisted
      reduce-scatter-allgather all-reduce (§IV-A).

Autodiff: each primitive carries a ``custom_vjp`` whose backward applies the
transpose collective under the *backward-direction* codec (paper §III-A:
gradients crossing MP collectives in the backward pass get the MP codec).
Compression itself is straight-through for gradients — it is a wire-level,
semantically-identity transform.

Codec state: stateful codecs (``ef:*`` error-feedback residuals, ``plr*``
low-rank warm factors — see :mod:`repro.core.codecs`) are carried-state
transforms, supported at the optimizer's flat dp/zero sync sites
(``psum`` outside autodiff, ``reduce_scatter_flat``, ``all_gather_flat``).
The trainer threads the state pytree through the jitted step next to
``opt_state`` and binds it around the optimizer with
:class:`codec_state_io`; each site reads its slot (keyed by the site's
ledger tag), rides the wire, and writes the updated state back.  A
stateful codec resolving at an autodiff or hierarchical-stage site raises
at trace time with the rule to exempt it — gradients are where the
carried-state math (and the paper's aggressive-DP-compression story)
applies.

Hierarchy: every public entry point accepts ``axis`` as a plain name, a
plain tuple of names (stock single-stage collective over the joint axis),
or a :class:`repro.core.compat.AxisPair` ``(outer, inner)``.  An
``AxisPair`` routes the call through the two-level hierarchical
decomposition (``hier_*`` below): the inner stage rides fast intra-node
links under the ``<tag>_inner`` codec, the outer stage rides slow
inter-node links under ``<tag>_outer`` (ZeRO++-style, arXiv:2306.10209).
Model code never hard-codes this — it passes ``MeshInfo.tp_axes`` (or
``launch.mesh.comm_axes``), which resolves a logical axis to the flat name
or the factored pair depending on the mesh.

All functions must be called inside ``shard_map`` over a mesh that defines
the named axis (or both sub-axes of an ``AxisPair``).
"""

from __future__ import annotations

import collections
import functools
import threading

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import codecs, compat, policy
from repro.kernels import ops
from repro.kernels.ref import BLOCK

# re-exported: the structured comm tag call sites pass instead of strings
Site = policy.Site
site = policy.site


# --------------------------------------------------------------------------
# traffic recorder (trace-time, static shapes): benchmarks and the roofline
# cross-check read this.
# --------------------------------------------------------------------------

_rec = threading.local()


class _EventLog(list):
    """The ledger ``record_traffic`` yields: the list itself holds the
    analytic per-call events (``_account``), and ``.wire`` the measured
    per-phase wire events the low-level impls emit (``_log``) — actual
    encoded-pytree bytes per hop, so analytic pricing can be cross-checked
    against what the rings really put on the links."""

    def __init__(self):
        super().__init__()
        self.wire = []


class record_traffic:
    """Trace-time collective ledger.

    Every public comms call appends one event with the *local* payload
    element count, the axis size, both codecs, and the current scan
    multiplier (layers per scanned group).  ``analysis.roofline`` turns
    events into per-device link bytes with the formulas:

        all_gather      (n-1) * E * bpv          (ring, E = local elems)
        reduce_scatter  (n-1)/n * E * bpv        (E = full local array)
        all_reduce      2 (n-1)/n * E * bpv      (RS + AG of compressed chunk)
        ppermute        E * bpv
        all_to_all      (n-1)/n * E * bpv

    with bpv = codec.wire_bits_per_value(dtype)/8.  The backward twin of a
    collective (its transpose under the bwd codec) moves the same element
    count, so training traffic = fwd + analytic bwd.  Ring-lowered events
    (compressed all-reduce / reduce-scatter) additionally carry a ``ring``
    fact — the hop schedule :func:`_ring_schedule` actually ran (row
    partition, realized bidir, fallback) — so the roofline prices the
    exact per-hop wire payloads, tile padding and all.

    The yielded object is a list (the analytic events) with a ``.wire``
    attribute: the measured wire events from the low-level impls (actual
    ``ops.wire_nbytes`` per hop payload, hop count, phase op, site tag)."""

    def __enter__(self):
        self.events = _EventLog()
        _rec.events = self.events
        return self.events

    def __exit__(self, *exc):
        del _rec.events
        return False


class scope_mult:
    """Multiplier for collectives traced once inside a scanned group.

    ``remat=True`` marks events whose forward collective re-executes during
    the rematerialized backward pass (fwd count = 2 in training)."""

    def __init__(self, n: int, remat: bool = False):
        self.n = n
        self.remat = remat

    def __enter__(self):
        self.prev = getattr(_rec, "mult", 1)
        self.prev_remat = getattr(_rec, "remat", False)
        _rec.mult = self.prev * self.n
        _rec.remat = self.prev_remat or self.remat
        return self

    def __exit__(self, *exc):
        _rec.mult = self.prev
        _rec.remat = self.prev_remat
        return False


class scope_facts:
    """Attach extra key/value facts to every ledger event traced inside.

    The pipeline trainer wraps its tick scan in ``scope_facts(vpp=V)`` so
    each handoff event records which interleaved schedule produced it —
    the roofline re-derives bubble / handoff terms from the fact instead
    of guessing the schedule from the event counts.  Facts merge into both
    the analytic events (:func:`_account`) and the measured wire events
    (:func:`_log`); inner scopes shadow outer keys."""

    def __init__(self, **facts):
        self.facts = facts

    def __enter__(self):
        self.prev = getattr(_rec, "facts", None)
        _rec.facts = {**(self.prev or {}), **self.facts}
        return self

    def __exit__(self, *exc):
        if self.prev is None:
            del _rec.facts
        else:
            _rec.facts = self.prev
        return False


class mute_ledger:
    """Temporarily detach the event log (events traced inside are dropped).

    Used where one logical collective is traced more than once — e.g.
    ``lax.cond`` over a rematerialized vs plain stage body traces both
    branches, but only one runs per tick; accounting both would double the
    ledger."""

    def __enter__(self):
        self.events = getattr(_rec, "events", None)
        if self.events is not None:
            del _rec.events
        return self

    def __exit__(self, *exc):
        if self.events is not None:
            _rec.events = self.events
        return False


def _account(op, tag, x, axis, c_fwd, c_bwd, bwd_op=None, level="flat",
             elems=None, nbytes=None):
    """Append one ledger event.

    ``level`` distinguishes the link class a collective rides: "flat" for
    single-stage collectives over an unfactored axis, "inner" for the
    intra-node stage of a hierarchical collective (fast links), "outer"
    for its inter-node stage (slow links).  ``elems`` overrides the local
    payload element count for stages that operate on a sub-chunk.
    ``nbytes`` records the payload size the CODEC RESOLUTION saw (it can
    differ from ``elems * itemsize`` — pro-rated partial permutations,
    hier stage chunks), so ``roofline.recost_events`` re-resolves
    size-threshold rules exactly as the live trace did."""
    events = getattr(_rec, "events", None)
    if events is None:
        return
    if level == "flat" and tag.endswith(("_inner", "_outer")):
        # a level-tagged single-stage call (e.g. the optimizer's staged
        # flat-vector sync) is itself one stage of a hierarchical op
        level = tag.rsplit("_", 1)[1]
    leaves = jax.tree_util.tree_leaves(x)
    if elems is None:
        elems = sum(l.size for l in leaves)
    dt = leaves[0].dtype if leaves else jnp.float32
    if nbytes is None:
        nbytes = int(elems) * jnp.dtype(dt).itemsize
    n = int(compat.axis_size(axis))
    ev = dict(
        op=op, tag=tag, axis=axis, n=n,
        elems=int(elems), dtype=str(dt), nbytes=int(nbytes),
        codec_fwd=c_fwd.name, codec_bwd=c_bwd.name,
        bwd_op=bwd_op, mult=int(getattr(_rec, "mult", 1)),
        remat=bool(getattr(_rec, "remat", False)),
        bidir=_bidir(), level=level)
    ev.update(getattr(_rec, "facts", None) or {})
    # ring facts: the hop schedule a compressed lowering of this event
    # would run (codec-independent — recost re-prices the same event under
    # candidate codecs in either direction, so the facts must not depend
    # on which codec happened to resolve here).  ``rows`` is the padded
    # per-rank chunk height the ring actually permutes.
    if op in ("all_reduce", "reduce_scatter") and n > 1:
        sched = _ring_schedule(ops.padded_rows(-(-int(elems) // n)))
        ev["ring"] = dict(rows=sched.rows, hops=n - 1,
                          parts=[list(p) for p in sched.parts],
                          bidir=sched.bidir, fallback=sched.fallback,
                          chunks=sched.chunks)
    events.append(ev)


def _log(op, tag, codec, payload_bytes, hops, **facts):
    """Measured wire event: ``payload_bytes`` actual encoded bytes put on
    the link per hop (``ops.wire_nbytes`` of the real wire pytree, tile
    padding included), repeated ``hops`` times.  Extra ``facts`` (the ring
    schedule's realized part count / bidir / fallback) make what actually
    ran visible next to the analytic events."""
    events = getattr(_rec, "events", None)
    if events is None or not hasattr(events, "wire"):
        return
    if not tag or tag == "-":
        tag = getattr(_rec, "wire_tag", "-")
    scoped = getattr(_rec, "facts", None) or {}
    events.wire.append(dict(
        op=op, tag=tag, codec=codec.name, payload_bytes=int(payload_bytes),
        hops=int(hops), mult=int(getattr(_rec, "mult", 1)),
        **{**scoped, **facts}))


class _wire_site:
    """Best-effort site tag for the measured wire events: the public
    wrappers bind their site's ledger tag around the (eagerly traced)
    forward impl, so ``_log`` can attribute hops to a site.  Backward
    impls trace later, outside any binding, and fall back to "-"."""

    def __init__(self, tag: str):
        self.tag = tag

    def __enter__(self):
        self.prev = getattr(_rec, "wire_tag", "-")
        _rec.wire_tag = self.tag
        return self

    def __exit__(self, *exc):
        _rec.wire_tag = self.prev
        return False


class ring_options:
    """Hillclimb levers for the compressed reduce-scatter rings.

    ``bidir``: split the payload rows in two and run simultaneous CW and
    CCW ppermute chains — each ICI link carries half the bytes (visible in
    HLO as paired collective-permutes).  The ledger credits the same
    2-link utilization to the XLA-native all-gather/all-to-all on the
    wire, which TPU tori perform bidirectionally anyway (EXPERIMENTS.md
    §Perf).

    ``chunks``: additionally split each directional ring into up to
    ``chunks`` independent row-striped sub-rings.  The sub-rings share no
    data dependencies, so the latency-hiding scheduler can overlap chunk
    *k*'s collective-permute with chunk *k+1*'s fused decode-add-encode —
    the transfer of one chunk hides behind the compute of the next.
    For bq codecs (scales per 128-lane row) chunk striping is bit-exact
    at any count under a fixed ``bidir`` setting; flipping ``bidir``
    itself reverses the hop order for half the rows (different fp
    addition order), and the per-tensor-scale ablation codec ``gq``
    changes scale granularity with any row partition — both already true
    of the pre-existing bidirectional split."""

    def __init__(self, bidir: bool, chunks: int = 1):
        assert chunks >= 1, f"ring chunks must be >= 1, got {chunks}"
        self.bidir = bidir
        self.chunks = chunks

    def __enter__(self):
        self.prev = getattr(_rec, "bidir", False)
        self.prev_chunks = getattr(_rec, "chunks", 1)
        _rec.bidir = self.bidir
        _rec.chunks = self.chunks
        return self

    def __exit__(self, *exc):
        _rec.bidir = self.prev
        _rec.chunks = self.prev_chunks
        return False


def _bidir() -> bool:
    return bool(getattr(_rec, "bidir", False))


def _ring_chunks() -> int:
    return int(getattr(_rec, "chunks", 1))


def _payload_nbytes(x) -> int:
    """Uncompressed local wire payload of ``x`` (a tensor or pytree) —
    the ``nbytes`` fact size-threshold rules match on."""
    leaves = jax.tree_util.tree_leaves(x)
    return int(sum(l.size * jnp.dtype(l.dtype).itemsize for l in leaves))


def _codec_pair(tag, nbytes: int | None = None):
    """(fwd, bwd) codecs for one single-stage collective.

    ``tag`` is a :class:`Site` or a legacy tag string; resolution goes
    through the active compiled :class:`~repro.core.policy.CommPlan`
    (an explicit ``policy.use_plan`` context, else the adapter plan of
    the thread-local scheme).  Sites pinning a direction (the
    optimizer's ``bwd`` gradient folds) or a level (one stage of a
    staged flat-vector sync) resolve to the same codec both ways."""
    return policy.current_plan().codec_pair(policy.as_site(tag), nbytes)


def _require_stateless(s, *cs):
    """Trace-time guard: carried-state codecs cannot ride autodiff twins —
    their state read/write has no home inside a ``custom_vjp`` backward.
    Optimizer-side collectives (traced inside ``codec_state_io``) are
    exempt per entry point: flat and hierarchical sum sites carry state,
    including per-level slots for the two-level decomposition."""
    for c in cs:
        if getattr(c, "stateful", False):
            raise NotImplementedError(
                f"stateful codec {c.name!r} resolved at site "
                f"{s.ledger_tag!r}: error-feedback / low-rank codecs ride "
                f"only the optimizer's sync sites (inside a "
                f"codec_state_io region), never autodiff traffic.  "
                f"Exempt this site with a policy rule, e.g. "
                f"Rule('bq8', dim='{s.dim}') ordered before the stateful "
                f"rule.")


# --------------------------------------------------------------------------
# codec-state io: the carried state of stateful codecs (ef:*, plr*)
# --------------------------------------------------------------------------

_state = threading.local()


class codec_state_io:
    """Bind the codec-state pytree for the optimizer's sync region.

    The trainer passes the step's codec-state dict (one slot per stateful
    site, keyed by the site's ledger tag — the template comes from
    ``CommPlan.codec_state_template``); each stateful comms site reads
    its slot and writes the updated state back.  ``collect()`` returns
    the post-region dict (same structure — slots of sites that did not
    fire, e.g. on a trivial axis, keep their old value), which the step
    returns next to ``opt_state``.  Thread-local, so parallel tracing
    stays correct."""

    def __init__(self, states: dict | None):
        self.states = dict(states or {})

    def __enter__(self):
        self.prev = getattr(_state, "io", None)
        _state.io = self
        return self

    def __exit__(self, *exc):
        _state.io = self.prev
        return False

    def read(self, key: str):
        try:
            return self.states[key]
        except KeyError:
            raise KeyError(
                f"no codec-state slot for site {key!r} (have "
                f"{sorted(self.states)}); the trainer's state template "
                f"(Trainer.codec_sites) does not cover this site — route "
                f"it to a stateless codec with a policy rule") from None

    def write(self, key: str, st):
        self.states[key] = st

    def collect(self) -> dict:
        return dict(self.states)


def _state_slot(s, c):
    """(io, key, state) for a stateful codec at a supported site."""
    io = getattr(_state, "io", None)
    key = s.ledger_tag
    if io is None:
        raise RuntimeError(
            f"stateful codec {c.name!r} resolved for site {key!r} outside "
            f"a codec-state region: ef:*/plr* codecs ride only the "
            f"optimizer's dp/zero sync sites, which the trainers wrap in "
            f"comms.codec_state_io(...).  Route this site to a stateless "
            f"codec with a policy rule (e.g. Rule('bq8', dim='{s.dim}')).")
    return io, key, io.read(key)


def _stateful_ok() -> bool:
    """True inside a ``codec_state_io`` region — the optimizer's sync
    scope, where carried-state codecs have a home.  Autodiff traffic
    (the model's fwd/bwd collectives) traces OUTSIDE the region, so
    gating the stateful paths on this keeps the ``custom_vjp`` ban
    intact while letting the optimizer's directed/hierarchical folds
    (tp/pp/cp grad syncs) carry per-site (and per-level) state."""
    return getattr(_state, "io", None) is not None


# --------------------------------------------------------------------------
# tune io: runtime-tunable sites (the self-tuning controller's swap point)
# --------------------------------------------------------------------------

_tune = threading.local()


class tune_io:
    """Bind the runtime-tunable site table for one traced step.

    ``select`` maps a tunable site's ledger tag to a TRACED int32 rung
    index over :data:`repro.tune.ladder.RUNGS`; a registered site
    dispatches through ``lax.switch`` over the executable rungs instead
    of its plan-static codec, so the host-side controller changes a
    site's codec by feeding a different integer into the next step —
    zero retraces, zero recompiles (the compile-count assertion in
    ``tests/multidev/tune_check.py`` holds the step's jit cache at 1
    across swaps).  ``sig`` carries each site's signal accumulator
    (:mod:`repro.tune.tracker` layout); the switch branches add their
    per-step increment, psum-reduced over ``axes`` (all mesh axes) so
    the returned leaves are replicated.  Thread-local, like
    :class:`codec_state_io`; sites NOT in ``select`` are untouched."""

    def __init__(self, select: dict, sig: dict, axes=()):
        self.select = dict(select or {})
        self.sig = dict(sig or {})
        self.axes = tuple(axes)

    def __enter__(self):
        self.prev = getattr(_tune, "io", None)
        _tune.io = self
        return self

    def __exit__(self, *exc):
        _tune.io = self.prev
        return False

    def add_sig(self, key: str, inc):
        if self.axes:
            n = 1
            for a in self.axes:
                n *= int(axis_size(a))
            # mean over the mesh: ``count`` stays a true step count and
            # the payload/error sums become per-rank means (their ratios
            # — all the controller reads — are unchanged)
            inc = lax.psum(inc, self.axes) / n
        self.sig[key] = self.sig[key] + inc

    def collect(self) -> dict:
        return dict(self.sig)


def _tuned_site(s):
    """The active tune_io region iff ``s`` is registered as tunable."""
    tio = getattr(_tune, "io", None)
    if tio is not None and s.ledger_tag in tio.select:
        return tio
    return None


AxisPair = compat.AxisPair


def _is_pair(axis) -> bool:
    return isinstance(axis, compat.AxisPair)


def axis_size(axis) -> int:
    return compat.axis_size(axis)


def axis_index(axis):
    return compat.axis_index(axis)


_vma = threading.local()


class vma_mode:
    """Whether the surrounding shard_map tracks varying-manual-axes.

    The train step runs with ``check_vma=False`` (see train_step.py); in
    that mode every value is typed with an empty vma and
    ``pcast(to="varying")`` must NOT be inserted — its transpose
    (psum_invariant) rejects untyped values.
    All vma-cast helpers below become no-ops when this flag is off."""

    def __init__(self, checked: bool):
        self.checked = checked

    def __enter__(self):
        self.prev = getattr(_vma, "checked", True)
        _vma.checked = self.checked
        return self

    def __exit__(self, *exc):
        _vma.checked = self.prev
        return False


def _vma_checked() -> bool:
    return getattr(_vma, "checked", True)


def _ensure_varying(x, axis):
    """Cast to varying over ``axis`` iff not already (not idempotent).

    ``axis`` may be a name or a tuple of names (joint / factored axes)."""
    if not _vma_checked():
        return x
    axes = tuple(axis) if isinstance(axis, (tuple, list)) else (axis,)
    need = tuple(ax for ax in axes if ax not in jax.typeof(x).vma)
    if not need:
        return x
    return lax.pcast(x, need, to="varying")


# --------------------------------------------------------------------------
# block-layout helpers
# --------------------------------------------------------------------------

def _chunked_blocks(flat: jnp.ndarray, n: int) -> jnp.ndarray:
    """1-D f32 -> [n, M, BLOCK] with each of the n chunks tile-padded."""
    per = -(-flat.shape[0] // n)
    m = ops.padded_rows(per)
    flat = jnp.pad(flat.astype(jnp.float32), (0, n * m * BLOCK - flat.shape[0]))
    return flat.reshape(n, m, BLOCK)


def _split_for_scatter(x: jnp.ndarray, axis_dim: int, n: int):
    """x with x.shape[axis_dim] % n == 0 -> ([n, chunk_flat...] blocks, chunk_shape)."""
    s = x.shape[axis_dim]
    assert s % n == 0, f"dim {axis_dim} of size {s} not divisible by axis size {n}"
    chunk_shape = x.shape[:axis_dim] + (s // n,) + x.shape[axis_dim + 1:]
    xs = x.reshape(x.shape[:axis_dim] + (n, s // n) + x.shape[axis_dim + 1:])
    xs = jnp.moveaxis(xs, axis_dim, 0)  # [n, ..., s//n, ...]
    flat = xs.reshape(n, -1)
    m = ops.padded_rows(flat.shape[1])
    flat = jnp.pad(flat.astype(jnp.float32),
                   ((0, 0), (0, m * BLOCK - flat.shape[1])))
    return flat.reshape(n, m, BLOCK), chunk_shape


def _chunk_to_shape(chunk2d: jnp.ndarray, shape, dtype):
    return ops.from_blocks(chunk2d, shape, dtype)


# --------------------------------------------------------------------------
# the compressed ring (reduce-scatter core)
# --------------------------------------------------------------------------

_RING_TILE = 8  # pallas TILE_M: every sub-ring keeps sublane alignment

RingSchedule = collections.namedtuple(
    "RingSchedule", ["parts", "rows", "bidir", "fallback", "chunks"])


def _ring_schedule(m: int, bidir: bool | None = None,
                   chunks: int | None = None) -> RingSchedule:
    """Row partition of an ``[n, m, BLOCK]`` ring payload into independent
    sub-rings — the SINGLE source of truth for what the compressed
    reduce-scatter actually runs, consumed by both the implementation
    (:func:`_ring_reduce_scatter`) and the ledger (``_account`` attaches
    it as the event's ``ring`` fact), so recorded events can never drift
    from the executed schedule.

    ``parts`` is a tuple of ``(row_lo, row_hi, direction)`` sub-rings:
    the bidirectional split first (rows halved across a CW and a CCW
    ring — skipped, with ``fallback=True``, when the halves would break
    the 8-row pallas tile alignment), then each directional segment
    striped into up to ``ring_options.chunks`` tile-aligned chunks whose
    ppermute chains are data-independent (transfer/encode overlap).
    ``bidir`` / ``chunks`` record what was REALIZED, not what was asked
    for.  The explicit ``bidir``/``chunks`` arguments let the roofline
    re-derive the schedule an event would run outside the trace-time
    thread-locals (which are the defaults)."""
    want_bidir = _bidir() if bidir is None else bool(bidir)
    want_chunks = _ring_chunks() if chunks is None else int(chunks)
    half = (m // 2) // _RING_TILE * _RING_TILE
    bidir = want_bidir and half >= _RING_TILE
    fallback = want_bidir and not bidir
    segs = [(0, half, +1), (half, m, -1)] if bidir else [(0, m, +1)]
    parts = []
    realized = 1
    for lo, hi, d in segs:
        tiles = (hi - lo) // _RING_TILE
        k = max(1, min(want_chunks, tiles))
        realized = max(realized, k)
        base, rem = divmod(tiles, k)
        at = lo
        for i in range(k):
            rows = (base + (1 if i < rem else 0)) * _RING_TILE
            parts.append((at, at + rows, d))
            at += rows
        assert at == hi
    return RingSchedule(tuple(parts), m, bidir, fallback, realized)


def _ring_rs_dir(xb, axis, codec, direction: int, want_wire: bool = True):
    """One directional ring (direction=+1 CW, -1 CCW).  Rank i ends owning
    the full sum of chunk i.  Returns ``(acc, wire, hop_nbytes)``.

    Intermediate hops run the wire-only fused decode-add-encode kernel
    (the f32 running sum is never read between hops, so it is never
    written); the final hop either emits the fused wire+sum pair
    (``want_wire`` — the all-reduce path gathers the compressed chunk) or
    just the sum (plain reduce-scatter: the re-encode would be dead
    code)."""
    n = xb.shape[0]
    idx = lax.axis_index(axis)
    perm = [(j, (j + direction) % n) for j in range(n)]

    def take(k):
        return lax.dynamic_index_in_dim(xb, k % n, axis=0, keepdims=False)

    acc = take(idx - direction)
    wire = codec.encode_blocks(acc)
    hop_nbytes = ops.wire_nbytes(wire)
    for t in range(n - 1):
        wire = jax.tree.map(lambda l: lax.ppermute(l, axis, perm), wire)
        local = take(idx - direction * (2 + t))
        if t < n - 2:
            wire, _ = codec.decode_add_encode_blocks(wire, local,
                                                     want_sum=False)
        elif want_wire:
            wire, acc = codec.decode_add_encode_blocks(wire, local)
        else:
            acc = codec.decode_add_blocks(wire, local)
            wire = None
    return acc, wire, hop_nbytes


def _ring_reduce_scatter(xb: jnp.ndarray, axis: str, codec: codecs.BqCodec,
                         want_wire: bool = True):
    """xb: [n, M, BLOCK] per-device addends -> (sum chunk [M, BLOCK] f32 owned
    by this rank (canonical: rank i owns chunk i), final compressed wire —
    ``None`` when ``want_wire`` is off and a final re-encode would be dead).

    The row partition comes from :func:`_ring_schedule`: the bidirectional
    split halves per-link bytes across opposite-direction rings, and chunk
    striping yields data-independent sub-rings the scheduler overlaps.
    Row-striping is bit-exact (bq scales are per 128-lane row), so any
    schedule produces identical sums and wires to the monolithic ring.
    Logs one measured ``rs_ring`` wire event: actual encoded bytes per hop
    across all sub-rings x (n-1) hops, stamped with the realized schedule
    (parts / bidir / fallback)."""
    n, m = xb.shape[0], xb.shape[1]
    sched = _ring_schedule(m)
    accs, wires, hop_nbytes = [], [], 0
    for lo, hi, d in sched.parts:
        part = xb if len(sched.parts) == 1 else xb[:, lo:hi]
        acc, wire, nb = _ring_rs_dir(part, axis, codec, d,
                                     want_wire=want_wire)
        accs.append(acc)
        wires.append(wire)
        hop_nbytes += nb
    _log("rs_ring", "-", codec, hop_nbytes, n - 1,
         parts=len(sched.parts), bidir=sched.bidir, fallback=sched.fallback)
    if len(sched.parts) == 1:
        return accs[0], wires[0]
    acc = jnp.concatenate(accs, axis=0)
    wire = None if not want_wire else jax.tree.map(
        lambda *ls: jnp.concatenate(ls, axis=0), *wires)
    return acc, wire


# --------------------------------------------------------------------------
# primitive implementations (no autodiff)
# --------------------------------------------------------------------------

def _psum_impl(x, axis, codec):
    if codec.is_identity:
        _log("all_reduce", "-", codec, 2 * x.size * x.dtype.itemsize, 1)
        return lax.psum(x, axis)
    n = axis_size(axis)
    if n == 1:
        return x
    xb = _chunked_blocks(x.reshape(-1), n)
    acc, wire = _ring_reduce_scatter(xb, axis, codec)
    del acc  # the all-reduce gathers the final compressed chunk instead
    gathered = jax.tree.map(
        lambda l: lax.all_gather(l, axis, axis=0, tiled=False), wire)
    _log("ar_allgather", "-", codec, ops.wire_nbytes(wire), n - 1)
    full = codec.decode_blocks(gathered)            # [n, M, BLOCK]
    flat = full.reshape(-1)[: x.size]
    return flat.reshape(x.shape).astype(x.dtype)


def _reduce_scatter_impl(x, axis, axis_dim, codec):
    n = axis_size(axis)
    if n == 1:
        return x
    if codec.is_identity:
        _log("reduce_scatter", "-", codec, x.size * x.dtype.itemsize, 1)
        return lax.psum_scatter(x, axis, scatter_dimension=axis_dim, tiled=True)
    xb, chunk_shape = _split_for_scatter(x, axis_dim, n)
    # want_wire=False: the ring logs its own per-hop wire bytes (rs_ring)
    # and skips the dead final re-encode
    acc, _ = _ring_reduce_scatter(xb, axis, codec, want_wire=False)
    return _chunk_to_shape(acc, chunk_shape, x.dtype)


def _all_gather_impl(x, axis, axis_dim, codec):
    n = axis_size(axis)
    if n == 1:
        return x
    if codec.is_identity:
        _log("all_gather", "-", codec, x.size * x.dtype.itemsize, n - 1)
        return lax.all_gather(x, axis, axis=axis_dim, tiled=True)
    wire, _ = codec.encode(x)
    _log("all_gather", "-", codec, ops.wire_nbytes(wire), n - 1)
    gathered = jax.tree.map(
        lambda l: lax.all_gather(l, axis, axis=0, tiled=False), wire)
    blocks = codec.decode_blocks(gathered)                    # [n, M, BLOCK]
    # strip each shard's tile padding BEFORE concatenating shards
    flat = blocks.reshape(n, -1)[:, :x.size]
    parts = flat.reshape((n,) + x.shape).astype(x.dtype)
    out = jnp.moveaxis(parts, 0, axis_dim)                    # [..., n, s, ...]
    shape = list(x.shape)
    shape[axis_dim] *= n
    return out.reshape(shape)


def _ppermute_impl(x, axis, perm, codec):
    if codec.is_identity:
        _log("ppermute", "-", codec, x.size * x.dtype.itemsize, 1)
        return lax.ppermute(x, axis, perm)
    wire, _ = codec.encode(x)
    _log("ppermute", "-", codec, ops.wire_nbytes(wire), 1)
    wire = jax.tree.map(lambda l: lax.ppermute(l, axis, perm), wire)
    return codec.decode(wire, x.shape, x.dtype)


def _all_to_all_impl(x, axis, split_axis, concat_axis, codec):
    n = axis_size(axis)
    if n == 1:
        return x
    if codec.is_identity:
        _log("all_to_all", "-", codec,
             x.size * x.dtype.itemsize * (n - 1) // n, 1)
        return lax.all_to_all(x, axis, split_axis=split_axis,
                              concat_axis=concat_axis, tiled=True)
    # slice along split_axis, encode each slice, exchange wire, reassemble
    xb, chunk_shape = _split_for_scatter(x, split_axis, n)   # [n, M, BLOCK]
    wire = codec.encode_blocks(xb)
    _log("all_to_all", "-", codec,
         ops.wire_nbytes(wire) * (n - 1) // n, 1)
    wire = jax.tree.map(
        lambda l: lax.all_to_all(l, axis, split_axis=0, concat_axis=0,
                                 tiled=True), wire)
    parts = codec.decode_blocks(wire)                        # [n, M, BLOCK]
    per = 1
    for d in chunk_shape:
        per *= d
    parts = parts.reshape(n, -1)[:, :per].reshape((n,) + chunk_shape)
    out = jnp.moveaxis(parts, 0, concat_axis)
    shape = list(chunk_shape)
    shape[concat_axis] *= n
    return out.reshape(shape).astype(x.dtype)


# --------------------------------------------------------------------------
# autodiff-aware public API
# --------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def _psum_vjp(x, axis, c_fwd, c_bwd):
    return _psum_impl(x, axis, c_fwd)


def _psum_fwd(x, axis, c_fwd, c_bwd):
    return _psum_impl(x, axis, c_fwd), None


def _psum_bwd(axis, c_fwd, c_bwd, _, g):
    return (_ensure_varying(_psum_impl(g, axis, c_bwd), axis),)


_psum_vjp.defvjp(_psum_fwd, _psum_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4))
def _ag_vjp(x, axis, axis_dim, c_fwd, c_bwd):
    return _all_gather_impl(x, axis, axis_dim, c_fwd)


def _ag_fwd(x, axis, axis_dim, c_fwd, c_bwd):
    return _all_gather_impl(x, axis, axis_dim, c_fwd), None


def _ag_bwd(axis, axis_dim, c_fwd, c_bwd, _, g):
    return (_reduce_scatter_impl(g, axis, axis_dim, c_bwd),)


_ag_vjp.defvjp(_ag_fwd, _ag_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4))
def _rs_vjp(x, axis, axis_dim, c_fwd, c_bwd):
    return _reduce_scatter_impl(x, axis, axis_dim, c_fwd)


def _rs_fwd(x, axis, axis_dim, c_fwd, c_bwd):
    return _reduce_scatter_impl(x, axis, axis_dim, c_fwd), None


def _rs_bwd(axis, axis_dim, c_fwd, c_bwd, _, g):
    return (_all_gather_impl(g, axis, axis_dim, c_bwd),)


_rs_vjp.defvjp(_rs_fwd, _rs_bwd)


def _invert_perm(perm):
    return [(d, s) for (s, d) in perm]


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4))
def _pp_vjp(x, axis, perm, c_fwd, c_bwd):
    return _ppermute_impl(x, axis, perm, c_fwd)


def _pp_fwd(x, axis, perm, c_fwd, c_bwd):
    return _ppermute_impl(x, axis, perm, c_fwd), None


def _pp_bwd(axis, perm, c_fwd, c_bwd, _, g):
    return (_ppermute_impl(g, axis, _invert_perm(perm), c_bwd),)


_pp_vjp.defvjp(_pp_fwd, _pp_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4, 5))
def _a2a_vjp(x, axis, split_axis, concat_axis, c_fwd, c_bwd):
    return _all_to_all_impl(x, axis, split_axis, concat_axis, c_fwd)


def _a2a_fwd(x, axis, split_axis, concat_axis, c_fwd, c_bwd):
    return _all_to_all_impl(x, axis, split_axis, concat_axis, c_fwd), None


def _a2a_bwd(axis, split_axis, concat_axis, c_fwd, c_bwd, _, g):
    return (_all_to_all_impl(g, axis, concat_axis, split_axis, c_bwd),)


_a2a_vjp.defvjp(_a2a_fwd, _a2a_bwd)


# ---- Megatron conjugate pair: g (copy fwd / all-reduce bwd) and
#      f (all-reduce fwd / copy bwd) -------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _g_vjp(x, axis, c_bwd):
    return x


def _g_fwd(x, axis, c_bwd):
    return x, None


def _g_bwd(axis, c_bwd, _, g):
    return (_ensure_varying(_psum_impl(g, axis, c_bwd), axis),)


_g_vjp.defvjp(_g_fwd, _g_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _f_vjp(x, axis, c_fwd):
    return _psum_impl(x, axis, c_fwd)


def _f_fwd(x, axis, c_fwd):
    return _psum_impl(x, axis, c_fwd), None


def _f_bwd(axis, c_fwd, _, g):
    return (_ensure_varying(g, axis),)


_f_vjp.defvjp(_f_fwd, _f_bwd)


# --------------------------------------------------------------------------
# public, site-resolving entry points.
#
# ``tag`` is a :class:`Site` (structured: dim / name / pinned direction or
# level) or a legacy tag string parsed into one.  Codec resolution goes
# through the active compiled CommPlan (policy.use_plan, else the adapter
# plan of the thread-local scheme).
#
# ``axis`` may be a name, a plain tuple (flat collective over the joint
# axis), or an AxisPair (outer, inner) — which routes through the two-level
# hierarchical decomposition with per-level codecs (hier_* below).
# --------------------------------------------------------------------------

def psum(x, axis, tag):
    """All-reduce-sum over ``axis`` under the active plan's codec for ``tag``.

    AxisPair axes route to :func:`hier_all_reduce`.  A stateful codec
    (``ef:*``/``plr*``) routes through the carried-state sum path — valid
    only at the optimizer's sync sites (inside ``codec_state_io``), never
    under autodiff."""
    s = policy.as_site(tag)
    if _is_pair(axis):
        return hier_all_reduce(x, axis.inner, axis.outer, s)
    c_fwd, c_bwd = _codec_pair(s, _payload_nbytes(x))
    if _tuned_site(s) is not None and axis_size(axis) > 1:
        with _wire_site(s.ledger_tag):
            return _tuned_psum(x, axis, s, c_fwd)
    if c_fwd.stateful or c_bwd.stateful:
        if s.dim in policy.DIRECTED_DIMS and not _stateful_ok():
            _require_stateless(s, c_fwd, c_bwd)  # raises: autodiff traffic
        with _wire_site(s.ledger_tag):
            return _stateful_psum(x, axis, s, c_fwd)
    _account("all_reduce", s.ledger_tag, x, axis, c_fwd, c_bwd,
             bwd_op="all_reduce", level=s.level or "flat")
    with _wire_site(s.ledger_tag):
        return _psum_vjp(x, axis, c_fwd, c_bwd)


def all_gather(x, axis, axis_dim: int, tag):
    """All-gather dim ``axis_dim`` over ``axis`` (bwd: reduce-scatter under
    the ``tag`` bwd codec).  AxisPair axes route to :func:`hier_all_gather`."""
    s = policy.as_site(tag)
    if _is_pair(axis):
        return hier_all_gather(x, axis.inner, axis.outer, axis_dim, s)
    c_fwd, c_bwd = _codec_pair(s, _payload_nbytes(x))
    _require_stateless(s, c_fwd, c_bwd)
    _account("all_gather", s.ledger_tag, x, axis, c_fwd, c_bwd,
             bwd_op="reduce_scatter", level=s.level or "flat")
    with _wire_site(s.ledger_tag):
        return _ag_vjp(x, axis, axis_dim, c_fwd, c_bwd)


def reduce_scatter(x, axis, axis_dim: int, tag):
    """Sum-reduce-scatter dim ``axis_dim`` over ``axis`` (bwd: all-gather).
    AxisPair axes route to :func:`hier_reduce_scatter`."""
    s = policy.as_site(tag)
    if _is_pair(axis):
        return hier_reduce_scatter(x, axis.inner, axis.outer, axis_dim, s)
    c_fwd, c_bwd = _codec_pair(s, _payload_nbytes(x))
    _require_stateless(s, c_fwd, c_bwd)
    _account("reduce_scatter", s.ledger_tag, x, axis, c_fwd, c_bwd,
             bwd_op="all_gather", level=s.level or "flat")
    with _wire_site(s.ledger_tag):
        return _rs_vjp(x, axis, axis_dim, c_fwd, c_bwd)


def ppermute(x, axis, perm, tag):
    """Point-to-point permutation over ``axis`` (bwd: inverse perm under the
    ``tag`` bwd codec).  With an AxisPair axis, ``perm`` indexes the joint
    (outer-major) rank space and routes to :func:`hier_ppermute`, which
    sends intra-node edges under the ``<tag>_inner`` codec and node-crossing
    edges under ``<tag>_outer``."""
    s = policy.as_site(tag)
    if _is_pair(axis):
        return hier_ppermute(x, axis.inner, axis.outer, perm, s)
    nbytes = _payload_nbytes(x)
    c_fwd, c_bwd = _codec_pair(s, nbytes)
    _require_stateless(s, c_fwd, c_bwd)
    perm = tuple(perm)
    # pro-rate partial permutations: only len(perm)/n ranks send, so the
    # average per-device bytes scale by the edge fraction (matches the
    # per-edge-class accounting of hier_ppermute; full rings unchanged)
    n = int(axis_size(axis))
    _account("ppermute", s.ledger_tag, x, axis, c_fwd, c_bwd,
             bwd_op="ppermute", elems=x.size * len(perm) // n,
             level=s.level or "flat", nbytes=nbytes)
    with _wire_site(s.ledger_tag):
        return _pp_vjp(x, axis, perm, c_fwd, c_bwd)


def stage_send(x, axis, tag="pp"):
    """Pipeline stage handoff: stage ``s`` sends ``x`` to stage ``s + 1``.

    The canonical forward edge of the 1F1B schedule — a partial (no
    wraparound) shift along the stage axis.  The last stage sends nothing;
    the first stage receives zeros (its real input is the embedded
    microbatch).  Encodes under the scheme's ``pp_fwd`` codec; the
    ``custom_vjp`` backward is the inverse shift (activation gradients
    flowing stage ``s+1 -> s``) under ``pp_bwd`` — so PP point-to-point
    traffic rides the compression path and the per-dimension ledger in
    both directions.  With an :class:`AxisPair` stage axis the handoff
    routes through :func:`hier_ppermute`: edges inside a node ride the
    ``pp_*_inner`` codec, node-crossing stage boundaries the aggressive
    ``pp_*_outer`` codec."""
    n = int(axis_size(axis))
    if n == 1:
        return jnp.zeros_like(x)
    return ppermute(x, axis, [(s, s + 1) for s in range(n - 1)], tag)


def stage_ring_send(x, axis, tag="pp"):
    """Wraparound stage handoff for the interleaved (vpp > 1) schedule:
    stage ``s`` sends ``x`` to stage ``(s + 1) % pp``.

    Under round-robin virtual stages the chunk after the last rank's
    slice ``v`` is the FIRST rank's slice ``v + 1`` — the activation must
    wrap, so this is a full ring rather than :func:`stage_send`'s partial
    shift.  Stage 0 consumes the wrapped value only when its live virtual
    stage has ``v > 0`` (otherwise its input is the embedded microbatch),
    and the last stage's final-slice output drains into the head instead
    of the ring — both maskings live in the tick schedule, not here.
    Same ``pp_fwd`` / ``pp_bwd`` codec routing and :class:`AxisPair`
    hierarchy handling as :func:`stage_send`."""
    n = int(axis_size(axis))
    if n == 1:
        return x
    return ppermute(x, axis, [(s, (s + 1) % n) for s in range(n)], tag)


def stage_recv(x, axis, tag="pp"):
    """Reverse stage shift: stage ``s`` sends ``x`` to stage ``s - 1``.

    The explicit backward-edge twin of :func:`stage_send` for schedules
    that hand gradients (or recomputation state) upstream themselves;
    its own ``custom_vjp`` backward is the forward shift.  Same codec /
    hierarchy routing as :func:`stage_send`."""
    n = int(axis_size(axis))
    if n == 1:
        return jnp.zeros_like(x)
    return ppermute(x, axis, [(s + 1, s) for s in range(n - 1)], tag)


def pool_handoff(x, axis, tag="kv@prefill_handoff", src: int = 0,
                 dst: int = 1):
    """Serving prefill->decode pool handoff: rank ``src`` of the pool
    axis sends ``x`` to rank ``dst``.

    A single-pair :func:`ppermute` (non-receiving pool ranks get zeros —
    the prefill pool drops its KV after the handoff), so the per-request
    KV transfer rides the compression path and the byte ledger under the
    serving ``kv`` dimension.  The event is pro-rated by the 1/n edge
    fraction like every partial permutation, and
    ``roofline.kv_handoff_seconds`` prices exactly these events."""
    if int(axis_size(axis)) == 1:
        return x
    return ppermute(x, axis, [(src, dst)], tag)


def all_to_all(x, axis, split_axis: int, concat_axis: int, tag):
    """All-to-all over ``axis`` (bwd: all-to-all with split/concat swapped).
    AxisPair axes route to :func:`hier_all_to_all`."""
    s = policy.as_site(tag)
    if _is_pair(axis):
        return hier_all_to_all(x, axis.inner, axis.outer, split_axis,
                               concat_axis, s)
    c_fwd, c_bwd = _codec_pair(s, _payload_nbytes(x))
    _require_stateless(s, c_fwd, c_bwd)
    _account("all_to_all", s.ledger_tag, x, axis, c_fwd, c_bwd,
             bwd_op="all_to_all", level=s.level or "flat")
    with _wire_site(s.ledger_tag):
        return _a2a_vjp(x, axis, split_axis, concat_axis, c_fwd, c_bwd)


def copy_fwd_psum_bwd(x, axis, tag):
    """Megatron 'g': identity forward, (compressed) all-reduce backward.

    AxisPair axes make the backward a two-level :func:`hier_all_reduce`
    under the ``<tag>_bwd_inner`` / ``<tag>_bwd_outer`` codecs."""
    s = policy.as_site(tag)
    nbytes = _payload_nbytes(x)
    if _is_pair(axis):
        n_i = int(axis_size(axis.inner))
        chunk = -(-x.size // n_i)
        (ci_f, ci_b), (co_f, co_b) = _hier_codec_pairs(
            s, nbytes, chunk * x.dtype.itemsize)
        _account_hier(
            [("none", axis.inner, "inner", x.size, "all_reduce"),
             ("none", axis.outer, "outer", chunk, "all_reduce")],
            s.ledger_tag, x, [(ci_f, ci_b), (co_f, co_b)],
            {"inner": nbytes, "outer": chunk * x.dtype.itemsize})
        return _hier_g_vjp(x, axis.inner, axis.outer, (ci_b, co_b))
    _, c_bwd = _codec_pair(s, nbytes)
    _require_stateless(s, c_bwd)
    _account("none", s.ledger_tag, x, axis, c_bwd, c_bwd,
             bwd_op="all_reduce", level=s.level or "flat")
    return _g_vjp(x, axis, c_bwd)


def psum_fwd_copy_bwd(x, axis, tag):
    """Megatron 'f': (compressed) all-reduce forward, identity backward.

    AxisPair axes make the forward a two-level :func:`hier_all_reduce`
    under the ``<tag>_fwd_inner`` / ``<tag>_fwd_outer`` codecs."""
    s = policy.as_site(tag)
    nbytes = _payload_nbytes(x)
    if _is_pair(axis):
        n_i = int(axis_size(axis.inner))
        chunk = -(-x.size // n_i)
        (ci_f, ci_b), (co_f, co_b) = _hier_codec_pairs(
            s, nbytes, chunk * x.dtype.itemsize)
        _account_hier(
            [("reduce_scatter", axis.inner, "inner", x.size, None),
             ("all_reduce", axis.outer, "outer", chunk, None),
             ("all_gather", axis.inner, "inner", chunk, None)],
            s.ledger_tag, x, [(ci_f, ci_b), (co_f, co_b), (ci_f, ci_b)],
            {"inner": nbytes, "outer": chunk * x.dtype.itemsize})
        with _wire_site(s.ledger_tag):
            return _hier_f_vjp(x, axis.inner, axis.outer, (ci_f, co_f))
    c_fwd, _ = _codec_pair(s, nbytes)
    _require_stateless(s, c_fwd)
    _account("all_reduce", s.ledger_tag, x, axis, c_fwd, c_fwd,
             bwd_op=None, level=s.level or "flat")
    with _wire_site(s.ledger_tag):
        return _f_vjp(x, axis, c_fwd)


# --------------------------------------------------------------------------
# hierarchical two-level collectives (ZeRO++-style, arXiv:2306.10209)
#
# A flat collective over one mesh axis is decomposed over a factored
# (outer=node, inner=local) pair of sub-axes:
#
#   all-reduce      = RS(inner, mild) -> AR(outer, aggressive) -> AG(inner, mild)
#   reduce-scatter  = RS(inner, mild) -> RS(outer, aggressive)
#   all-gather      = AG(outer, aggressive) -> AG(inner, mild)
#
# The inner stages ride fast intra-node links (NVLink/ICI) under a mild
# codec; the outer stage moves only a 1/n_inner chunk over the slow
# inter-node links (IB/DCN) under an aggressive codec — which is where the
# wire savings live.  Chunk assignment is linearized outer-major, so with
# identity codecs each op is equivalent to the stock ``lax`` collective
# over the joint ``(outer, inner)`` axis tuple.
# --------------------------------------------------------------------------

def _hier_codec_pairs(tag, nbytes_inner: int | None = None,
                      nbytes_outer: int | None = None,
                      allow_stateful: bool = False):
    """((inner_fwd, inner_bwd), (outer_fwd, outer_bwd)) for ``tag``.

    Resolved through the active compiled plan; a tag/site without
    level-constrained rules falls back to its flat codec (the adapter
    path preserves the legacy ``<tag>_<level> -> <tag>`` chain).
    ``nbytes_*`` carry the per-stage payload sizes — the outer stage of a
    two-level op moves only a 1/n_inner chunk, so size rules see what
    actually crosses the slow links.

    ``allow_stateful`` (hier_all_reduce only) admits carried-state codecs
    when a ``codec_state_io`` region is active — the optimizer's sync
    scope keeps per-LEVEL state slots (``<tag>_inner@...``), while
    autodiff-side hierarchical collectives trace outside the region and
    keep the stateless requirement."""
    s = policy.as_site(tag)
    pairs = policy.current_plan().hier_codec_pairs(s, nbytes_inner,
                                                   nbytes_outer)
    if not (allow_stateful and _stateful_ok()):
        _require_stateless(s, *pairs[0], *pairs[1])
    return pairs


def _hier_psum_impl(x, inner, outer, c_in, c_out):
    """RS(inner) -> AR(outer) -> AG(inner) on the flattened payload."""
    n_i = axis_size(inner)
    n_o = axis_size(outer)
    if n_i == 1 and n_o == 1:
        return x
    if n_i == 1:
        return _psum_impl(x, outer, c_out)
    total = x.size
    xb = _chunked_blocks(x.reshape(-1), n_i)            # [n_i, M, BLOCK] f32
    # stage 1: intra-node reduce-scatter — rank i owns sum-chunk i.  On a
    # single-node mesh (n_o == 1) the ring's final fused re-encode IS the
    # stage-3 wire, so keep it; otherwise the chunk changes in stage 2 and
    # the re-encode would be dead.
    wire = None
    if c_in.is_identity:
        chunk = lax.psum_scatter(xb, inner, scatter_dimension=0, tiled=False)
    else:
        chunk, wire = _ring_reduce_scatter(xb, inner, c_in,
                                           want_wire=(n_o == 1))
    # stage 2: inter-node all-reduce of the 1/n_i chunk
    if n_o > 1:
        chunk = _psum_impl(chunk, outer, c_out)
        wire = None
    # stage 3: intra-node all-gather of the fully-reduced chunks
    if c_in.is_identity:
        full = lax.all_gather(chunk, inner, axis=0, tiled=False)
    else:
        if wire is None:
            wire = c_in.encode_blocks(chunk)
        _log("ar_allgather", "-", c_in, ops.wire_nbytes(wire), n_i - 1)
        gathered = jax.tree.map(
            lambda l: lax.all_gather(l, inner, axis=0, tiled=False), wire)
        full = c_in.decode_blocks(gathered)             # [n_i, M, BLOCK]
    return full.reshape(-1)[:total].reshape(x.shape).astype(x.dtype)


def _hier_reduce_scatter_impl(x, inner, outer, axis_dim, c_in, c_out):
    """Scatter dim ``axis_dim`` over the joint axis, outer-major chunks."""
    n_i = axis_size(inner)
    n_o = axis_size(outer)
    n = n_i * n_o
    if n == 1:
        return x
    s = x.shape[axis_dim]
    assert s % n == 0, f"dim {axis_dim} of size {s} not divisible by {n}"
    pre, post = x.shape[:axis_dim], x.shape[axis_dim + 1:]
    xr = x.reshape(pre + (n_o, n_i, s // n) + post)
    y = _reduce_scatter_impl(xr, inner, axis_dim + 1, c_in)
    z = _reduce_scatter_impl(y, outer, axis_dim, c_out)
    return z.reshape(pre + (s // n,) + post)


def _hier_all_gather_impl(x, inner, outer, axis_dim, c_in, c_out):
    """Exact transpose of :func:`_hier_reduce_scatter_impl`."""
    n_i = axis_size(inner)
    n_o = axis_size(outer)
    if n_i * n_o == 1:
        return x
    s = x.shape[axis_dim]
    pre, post = x.shape[:axis_dim], x.shape[axis_dim + 1:]
    y = _all_gather_impl(x, outer, axis_dim, c_out)     # [..., n_o*s, ...]
    yr = y.reshape(pre + (n_o, 1, s) + post)
    z = _all_gather_impl(yr, inner, axis_dim + 1, c_in)  # [..., n_o, n_i, s, ...]
    return z.reshape(pre + (n_o * n_i * s,) + post)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4))
def _hier_psum_vjp(x, inner, outer, cs_in, cs_out):
    return _hier_psum_impl(x, inner, outer, cs_in[0], cs_out[0])


def _hier_psum_fwd(x, inner, outer, cs_in, cs_out):
    return _hier_psum_impl(x, inner, outer, cs_in[0], cs_out[0]), None


def _hier_psum_bwd(inner, outer, cs_in, cs_out, _, g):
    out = _hier_psum_impl(g, inner, outer, cs_in[1], cs_out[1])
    return (_ensure_varying(_ensure_varying(out, inner), outer),)


_hier_psum_vjp.defvjp(_hier_psum_fwd, _hier_psum_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4, 5))
def _hier_rs_vjp(x, inner, outer, axis_dim, cs_in, cs_out):
    return _hier_reduce_scatter_impl(x, inner, outer, axis_dim,
                                     cs_in[0], cs_out[0])


def _hier_rs_fwd(x, inner, outer, axis_dim, cs_in, cs_out):
    return _hier_reduce_scatter_impl(x, inner, outer, axis_dim,
                                     cs_in[0], cs_out[0]), None


def _hier_rs_bwd(inner, outer, axis_dim, cs_in, cs_out, _, g):
    return (_hier_all_gather_impl(g, inner, outer, axis_dim,
                                  cs_in[1], cs_out[1]),)


_hier_rs_vjp.defvjp(_hier_rs_fwd, _hier_rs_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4, 5))
def _hier_ag_vjp(x, inner, outer, axis_dim, cs_in, cs_out):
    return _hier_all_gather_impl(x, inner, outer, axis_dim,
                                 cs_in[0], cs_out[0])


def _hier_ag_fwd(x, inner, outer, axis_dim, cs_in, cs_out):
    return _hier_all_gather_impl(x, inner, outer, axis_dim,
                                 cs_in[0], cs_out[0]), None


def _hier_ag_bwd(inner, outer, axis_dim, cs_in, cs_out, _, g):
    return (_hier_reduce_scatter_impl(g, inner, outer, axis_dim,
                                      cs_in[1], cs_out[1]),)


_hier_ag_vjp.defvjp(_hier_ag_fwd, _hier_ag_bwd)


def _account_hier(stages, tag, x, c_pairs, nbytes_by_level=None):
    """Ledger the per-stage events of one hierarchical op.

    ``stages`` is a list of (op, axis, level, elems, bwd_op); ``c_pairs``
    the matching (fwd, bwd) codec per stage.  ``nbytes_by_level`` records
    the per-level payload size the codec resolution saw (a stage's elems
    can be a sub-chunk of it)."""
    nbl = nbytes_by_level or {}
    for (op, axis, level, elems, bwd_op), (cf, cb) in zip(stages, c_pairs):
        _account(op, tag, x, axis, cf, cb, bwd_op=bwd_op, level=level,
                 elems=elems, nbytes=nbl.get(level))


def hier_all_reduce(x, inner_axis: str, outer_axis: str, tag):
    """Two-level all-reduce-sum over the factored ``(outer, inner)`` axes.

    Stage decomposition: ``RS(inner)`` of the flattened payload under the
    ``<tag>_inner`` codec (for directed tags: ``<tag>_fwd_inner``), then
    ``AR(outer)`` of the resulting ``1/n_inner`` chunk under
    ``<tag>_outer``, then ``AG(inner)`` of the fully-reduced chunks.  With
    identity codecs, bit-exact against ``lax.psum`` over the joint axis
    pair; the inter-node stage moves only ``1/n_inner`` of the payload
    under the (aggressive) outer codec — the slow-link saving.

    Backward: the same decomposition applied to the cotangent under the
    ``_bwd`` codecs (psum is self-transpose up to replication typing).
    Ledger: "inner" RS + "outer" AR + "inner" AG events."""
    s = policy.as_site(tag)
    n_i = int(axis_size(inner_axis))
    chunk = -(-x.size // n_i)
    nbytes = _payload_nbytes(x)
    (ci_f, ci_b), (co_f, co_b) = _hier_codec_pairs(
        s, nbytes, chunk * x.dtype.itemsize, allow_stateful=True)
    if any(c.stateful for c in (ci_f, ci_b, co_f, co_b)):
        # optimizer-side (inside codec_state_io, or _hier_codec_pairs
        # raised above): per-level carried state, no VJP twin
        return _stateful_hier_psum(x, inner_axis, outer_axis, s, ci_f, co_f)
    _account_hier(
        [("reduce_scatter", inner_axis, "inner", x.size, "all_gather"),
         ("all_reduce", outer_axis, "outer", chunk, "all_reduce"),
         ("all_gather", inner_axis, "inner", chunk, "reduce_scatter")],
        s.ledger_tag, x, [(ci_f, ci_b), (co_f, co_b), (ci_f, ci_b)],
        {"inner": nbytes, "outer": chunk * x.dtype.itemsize})
    with _wire_site(s.ledger_tag):
        return _hier_psum_vjp(x, inner_axis, outer_axis,
                              (ci_f, ci_b), (co_f, co_b))


# ZeRO++-style name kept alongside the lax-style one
hier_psum = hier_all_reduce


def hier_reduce_scatter(x, inner_axis: str, outer_axis: str, axis_dim: int,
                        tag):
    """Two-level reduce-scatter of dim ``axis_dim`` (outer-major chunks).

    Stages: ``RS(inner)`` under ``<tag>_inner`` (full payload, fast
    links), then ``RS(outer)`` of the surviving ``1/n_inner`` chunk under
    ``<tag>_outer`` (slow links).  Chunk assignment is linearized
    outer-major, so with identity codecs the result is bit-exact against
    ``lax.psum_scatter`` over the joint axis pair.  Backward:
    :func:`hier_all_gather` under the ``_bwd`` codecs."""
    s = policy.as_site(tag)
    n_i = int(axis_size(inner_axis))
    nbytes = _payload_nbytes(x)
    (ci_f, ci_b), (co_f, co_b) = _hier_codec_pairs(
        s, nbytes, x.size // n_i * x.dtype.itemsize)
    _account_hier(
        [("reduce_scatter", inner_axis, "inner", x.size, "all_gather"),
         ("reduce_scatter", outer_axis, "outer", x.size // n_i, "all_gather")],
        s.ledger_tag, x, [(ci_f, ci_b), (co_f, co_b)],
        {"inner": nbytes, "outer": x.size // n_i * x.dtype.itemsize})
    with _wire_site(s.ledger_tag):
        return _hier_rs_vjp(x, inner_axis, outer_axis, axis_dim,
                            (ci_f, ci_b), (co_f, co_b))


def hier_all_gather(x, inner_axis: str, outer_axis: str, axis_dim: int,
                    tag):
    """Two-level all-gather of dim ``axis_dim`` (transpose of hier RS).

    Stages: ``AG(outer)`` of the full local shard on slow links under
    ``<tag>_outer``, then ``AG(inner)`` of the node-gathered block on fast
    links under ``<tag>_inner``.  With identity codecs, bit-exact against
    ``lax.all_gather`` over the joint ``(outer, inner)`` axis pair
    (outer-major shard order).  Backward: :func:`hier_reduce_scatter`
    under the ``_bwd`` codecs.  Ledger: one "outer" + one "inner" event."""
    s = policy.as_site(tag)
    n_o = int(axis_size(outer_axis))
    nbytes = _payload_nbytes(x)
    (ci_f, ci_b), (co_f, co_b) = _hier_codec_pairs(s, nbytes * n_o, nbytes)
    _account_hier(
        [("all_gather", outer_axis, "outer", x.size, "reduce_scatter"),
         ("all_gather", inner_axis, "inner", x.size * n_o, "reduce_scatter")],
        s.ledger_tag, x, [(co_f, co_b), (ci_f, ci_b)],
        {"inner": nbytes * n_o, "outer": nbytes})
    with _wire_site(s.ledger_tag):
        return _hier_ag_vjp(x, inner_axis, outer_axis, axis_dim,
                            (ci_f, ci_b), (co_f, co_b))


# --------------------------------------------------------------------------
# hierarchical all-to-all (EP token routing) and point-to-point permutation
# (PP handoffs / ring hops) over a factored axis pair
# --------------------------------------------------------------------------

def _hier_all_to_all_impl(x, inner, outer, split_axis, concat_axis,
                          c_in, c_out):
    """Two-stage decomposition of the joint tiled all-to-all.

    Chunks along ``split_axis`` are indexed outer-major ``(co, ci)``;
    stage 1 exchanges the ``ci`` sub-index over ``inner`` (intra-node),
    stage 2 the ``co`` sub-index over ``outer`` (inter-node).  The result
    holds chunks in joint source-rank order — identical to the stock
    ``lax.all_to_all`` over the ``(outer, inner)`` axis tuple."""
    n_i = axis_size(inner)
    n_o = axis_size(outer)
    n = n_i * n_o
    if n == 1:
        return x
    if n_o == 1:
        return _all_to_all_impl(x, inner, split_axis, concat_axis, c_in)
    if n_i == 1:
        return _all_to_all_impl(x, outer, split_axis, concat_axis, c_out)
    s = x.shape[split_axis]
    assert s % n == 0, f"dim {split_axis} of size {s} not divisible by {n}"
    pre, post = x.shape[:split_axis], x.shape[split_axis + 1:]
    sa = split_axis
    xr = x.reshape(pre + (n_o, n_i, s // n) + post)
    y = _all_to_all_impl(xr, inner, sa + 1, sa + 1, c_in)   # swap ci intra-node
    z = _all_to_all_impl(y, outer, sa, sa, c_out)           # swap co inter-node
    z = z.reshape(pre + (n, s // n) + post)                 # joint source order
    if concat_axis == split_axis:
        return z.reshape(pre + (s,) + post)
    chunk_shape = pre + (s // n,) + post
    parts = jnp.moveaxis(z, sa, 0)                          # [n, *chunk_shape]
    out = jnp.moveaxis(parts, 0, concat_axis)
    shape = list(chunk_shape)
    shape[concat_axis] *= n
    return out.reshape(shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4, 5, 6))
def _hier_a2a_vjp(x, inner, outer, split_axis, concat_axis, cs_in, cs_out):
    return _hier_all_to_all_impl(x, inner, outer, split_axis, concat_axis,
                                 cs_in[0], cs_out[0])


def _hier_a2a_fwd(x, inner, outer, split_axis, concat_axis, cs_in, cs_out):
    return _hier_all_to_all_impl(x, inner, outer, split_axis, concat_axis,
                                 cs_in[0], cs_out[0]), None


def _hier_a2a_bwd(inner, outer, split_axis, concat_axis, cs_in, cs_out, _, g):
    return (_hier_all_to_all_impl(g, inner, outer, concat_axis, split_axis,
                                  cs_in[1], cs_out[1]),)


_hier_a2a_vjp.defvjp(_hier_a2a_fwd, _hier_a2a_bwd)


def hier_all_to_all(x, inner_axis: str, outer_axis: str, split_axis: int,
                    concat_axis: int, tag):
    """Two-stage all-to-all over the factored ``(outer, inner)`` axis pair.

    Stage decomposition (2D all-to-all, DeepSpeed-TED style): the chunk
    index splits outer-major into ``(co, ci)``; stage 1 exchanges ``ci``
    over the intra-node ``inner`` axis under the ``<tag>_fwd_inner`` codec,
    stage 2 exchanges ``co`` over the inter-node ``outer`` axis under
    ``<tag>_fwd_outer``.  With identity codecs, bit-exact against the stock
    tiled ``lax.all_to_all`` over the joint axis pair.  The inter-node
    byte volume equals the flat op's node-crossing fraction, so the
    slow-link savings come from the aggressive ``_outer`` codec.

    Backward: the transpose all-to-all (split/concat swapped) under the
    ``<tag>_bwd_inner`` / ``<tag>_bwd_outer`` codecs.
    Ledger: one "inner" event over ``inner_axis`` and one "outer" event
    over ``outer_axis``, each of the full local payload (per-device bytes
    scale by the usual (n-1)/n all-to-all factor per stage)."""
    s = policy.as_site(tag)
    nbytes = _payload_nbytes(x)
    (ci_f, ci_b), (co_f, co_b) = _hier_codec_pairs(s, nbytes, nbytes)
    _account_hier(
        [("all_to_all", inner_axis, "inner", x.size, "all_to_all"),
         ("all_to_all", outer_axis, "outer", x.size, "all_to_all")],
        s.ledger_tag, x, [(ci_f, ci_b), (co_f, co_b)],
        {"inner": nbytes, "outer": nbytes})
    with _wire_site(s.ledger_tag):
        return _hier_a2a_vjp(x, inner_axis, outer_axis, split_axis,
                             concat_axis, (ci_f, ci_b), (co_f, co_b))


def _hier_ppermute_impl(x, inner, outer, perm, c_in, c_out):
    """Edge-classified joint permutation.

    ``perm`` indexes the joint (outer-major) rank space.  Edges that stay
    inside a node ride the ``c_in`` codec; node-crossing edges the
    ``c_out`` codec.  Each rank receives along at most one edge (perm is a
    partial permutation), so the two classes merge with a per-rank
    select."""
    n_i = int(axis_size(inner))
    n_o = int(axis_size(outer))
    n = n_i * n_o
    if n == 1:
        return x
    if n_o == 1:
        return _ppermute_impl(x, inner, perm, c_in)
    if n_i == 1:
        return _ppermute_impl(x, outer, perm, c_out)
    joint = (outer, inner)
    intra = tuple((s, d) for s, d in perm if s // n_i == d // n_i)
    inter = tuple((s, d) for s, d in perm if s // n_i != d // n_i)
    if not inter:
        return _ppermute_impl(x, joint, intra, c_in)
    if not intra:
        return _ppermute_impl(x, joint, inter, c_out)
    y_in = _ppermute_impl(x, joint, intra, c_in)
    y_out = _ppermute_impl(x, joint, inter, c_out)
    recv_intra = [False] * n
    for _, d in intra:
        recv_intra[d] = True
    mask = jnp.asarray(recv_intra)[compat.axis_index(joint)]
    return jnp.where(mask, y_in, y_out)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4, 5))
def _hier_pp_vjp(x, inner, outer, perm, cs_in, cs_out):
    return _hier_ppermute_impl(x, inner, outer, perm, cs_in[0], cs_out[0])


def _hier_pp_fwd(x, inner, outer, perm, cs_in, cs_out):
    return _hier_ppermute_impl(x, inner, outer, perm, cs_in[0], cs_out[0]), \
        None


def _hier_pp_bwd(inner, outer, perm, cs_in, cs_out, _, g):
    out = _hier_ppermute_impl(g, inner, outer, _invert_perm(perm),
                              cs_in[1], cs_out[1])
    return (_ensure_varying(out, (inner, outer)),)


_hier_pp_vjp.defvjp(_hier_pp_fwd, _hier_pp_bwd)


def hier_ppermute(x, inner_axis: str, outer_axis: str, perm, tag):
    """Edge-classified point-to-point permutation over the factored
    ``(outer, inner)`` axis pair.

    ``perm`` is ``[(src, dst), ...]`` in the *joint* (outer-major) rank
    space — exactly the perm a flat ``ppermute`` over the joint axis tuple
    would take.  Stage decomposition: edges whose endpoints share a node
    ride fast intra-node links under the ``<tag>_fwd_inner`` codec;
    node-crossing edges ride slow links under ``<tag>_fwd_outer``.  With
    identity codecs, bit-exact against ``lax.ppermute`` over the joint
    axis tuple.  Backward: the inverse permutation under the
    ``<tag>_bwd_*`` codecs (node-crossing-ness is preserved by inversion).
    Ledger: an "inner" event scaled by the intra-node edge fraction and an
    "outer" event scaled by the node-crossing fraction."""
    st = policy.as_site(tag)
    nbytes = _payload_nbytes(x)
    (ci_f, ci_b), (co_f, co_b) = _hier_codec_pairs(st, nbytes, nbytes)
    n_i = int(axis_size(inner_axis))
    n = n_i * int(axis_size(outer_axis))
    perm = tuple((int(s), int(d)) for s, d in perm)
    k_in = sum(1 for s, d in perm if s // n_i == d // n_i)
    k_out = len(perm) - k_in
    _account_hier(
        [("ppermute", inner_axis, "inner", x.size * k_in // n, "ppermute"),
         ("ppermute", outer_axis, "outer", x.size * k_out // n, "ppermute")],
        st.ledger_tag, x, [(ci_f, ci_b), (co_f, co_b)],
        {"inner": nbytes, "outer": nbytes})
    with _wire_site(st.ledger_tag):
        return _hier_pp_vjp(x, inner_axis, outer_axis, perm,
                            (ci_f, ci_b), (co_f, co_b))


# ---- hierarchical Megatron conjugate pair (decode-path f/g) --------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def _hier_g_vjp(x, inner, outer, c_bwds):
    return x


def _hier_g_fwd(x, inner, outer, c_bwds):
    return x, None


def _hier_g_bwd(inner, outer, c_bwds, _, g):
    out = _hier_psum_impl(g, inner, outer, c_bwds[0], c_bwds[1])
    return (_ensure_varying(out, (inner, outer)),)


_hier_g_vjp.defvjp(_hier_g_fwd, _hier_g_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def _hier_f_vjp(x, inner, outer, c_fwds):
    return _hier_psum_impl(x, inner, outer, c_fwds[0], c_fwds[1])


def _hier_f_fwd(x, inner, outer, c_fwds):
    return _hier_psum_impl(x, inner, outer, c_fwds[0], c_fwds[1]), None


def _hier_f_bwd(inner, outer, c_fwds, _, g):
    return (_ensure_varying(g, (inner, outer)),)


_hier_f_vjp.defvjp(_hier_f_fwd, _hier_f_bwd)


def match_vma(x, like):
    """Cast pytree ``x`` so its varying-axes type matches ``like``'s leaves.

    Needed wherever a freshly-created zeros/ones scan seed meets values that
    came through collectives (scan carries must be vma-stable)."""
    if not _vma_checked():
        return x
    vma = frozenset()
    for l in jax.tree_util.tree_leaves(like):
        vma = vma | jax.typeof(l).vma

    def f(l):
        need = tuple(vma - jax.typeof(l).vma)
        return lax.pcast(l, need, to="varying") if need else l
    return jax.tree.map(f, x)


def varying_all(x, axes):
    """Cast a pytree to varying over every mesh axis (idempotent) — used
    to give scan carries a stable vma type regardless of which collectives
    produced them."""
    if not _vma_checked():
        return x

    def f(l):
        for ax in axes:
            l = _ensure_varying(l, ax)
        return l
    return jax.tree.map(f, x)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def pmax(x, axis):
    """Max-reduce (never compressed: tiny softmax-stat payloads).

    ``axis`` may be a name or an AxisPair/tuple — max has no useful
    two-level codec treatment, so a factored axis reduces as the joint
    flat axis.

    Carries a zero VJP — its only use is as a numerics stabilizer (shift-
    invariant logsumexp), where the gradient contribution is exactly zero."""
    return lax.pmax(x, axis)


def _pmax_fwd(x, axis):
    return lax.pmax(x, axis), None


def _pmax_bwd(axis, res, g):
    return (_ensure_varying(jnp.zeros_like(g), axis),)


pmax.defvjp(_pmax_fwd, _pmax_bwd)


# --------------------------------------------------------------------------
# flat-vector paths for the optimizer (outside autodiff).  These are the
# sites that support carried-state codecs: the paper's aggressive-DP
# compression target is exactly this gradient sync.
# --------------------------------------------------------------------------

def reduce_scatter_flat(flat: jnp.ndarray, axis: str, tag="dp",
                        mean: bool = False) -> jnp.ndarray:
    """1-D sum-reduce-scatter: rank i returns padded chunk i (len ceil(n/axis)).

    Stateful codecs: ``ef:*`` compensates with the stashed residual, rides
    the inner codec's ring on the compensated vector, and stashes the new
    local quantization error; ``plr*`` runs the two-factor low-rank
    all-reduce and slices this rank's chunk of the reconstruction."""
    s = policy.as_site(tag)
    c, _ = _codec_pair(s, _payload_nbytes(flat))
    if _tuned_site(s) is not None and axis_size(axis) > 1:
        with _wire_site(s.ledger_tag):
            return _tuned_reduce_scatter_flat(flat, axis, s, c, mean)
    if c.stateful and axis_size(axis) > 1:
        with _wire_site(s.ledger_tag):
            return _stateful_reduce_scatter_flat(flat, axis, s, c, mean)
    if c.stateful:          # trivial axis: nothing crosses the wire
        c = codecs.NONE
    _account("reduce_scatter", s.ledger_tag, flat, axis, c, c, bwd_op=None,
             level=s.level or "flat")
    with _wire_site(s.ledger_tag):
        return _reduce_scatter_flat_impl(flat, axis, c, mean)


def _reduce_scatter_flat_impl(flat, axis, c, mean):
    n = axis_size(axis)
    if n == 1:
        # still tile-pad: consumers (the ZeRO-1 master chunk) size their
        # slice as padded_rows(ceil(n/axis)) * BLOCK even on a trivial axis
        m = ops.padded_rows(flat.shape[0])
        flat = jnp.pad(flat, (0, m * BLOCK - flat.shape[0]))
        return flat / n if mean else flat
    xb = _chunked_blocks(flat, n)
    if c.is_identity:
        _log("reduce_scatter", "-", c, flat.size * flat.dtype.itemsize, 1)
        chunk = lax.psum_scatter(xb, axis, scatter_dimension=0, tiled=False)
    else:
        chunk, _ = _ring_reduce_scatter(xb, axis, c, want_wire=False)
    chunk = chunk.reshape(-1)
    return chunk / n if mean else chunk


def all_gather_flat(chunk: jnp.ndarray, axis: str, total: int,
                    tag="zero") -> jnp.ndarray:
    """Inverse of reduce_scatter_flat: gather padded chunks, trim to ``total``.

    ``ef:*`` codecs compensate the local chunk before encoding (qwZ-style
    error feedback on the lossy param broadcast); low-rank codecs ride sum
    collectives only and raise here."""
    s = policy.as_site(tag)
    c, _ = _codec_pair(s, _payload_nbytes(chunk))
    if c.stateful and axis_size(axis) > 1:
        if c.kind != "ef" or c.inner.stateful:
            raise NotImplementedError(
                f"codec {c.name!r} at gather site {s.ledger_tag!r}: "
                "low-rank codecs ride sum collectives only (ef:<bq*> "
                "works on gathers)")
        io, key, st = _state_slot(s, c)
        xc = c.compensate(chunk, st)
        _account("all_gather", s.ledger_tag, xc, axis, c, c, bwd_op=None,
                 level=s.level or "flat")
        # one encode serves both the wire and the residual (unlike the
        # ring paths, the gathered wire IS the local encode)
        wire = c.inner.encode_blocks(xc.reshape(-1, BLOCK))
        dec = c.inner.decode_blocks(wire).reshape(xc.shape)
        io.write(key, {"residual": xc - dec})
        _log("all_gather", s.ledger_tag, c, ops.wire_nbytes(wire),
             axis_size(axis) - 1)
        gathered = jax.tree.map(
            lambda l: lax.all_gather(l, axis, axis=0, tiled=True), wire)
        return c.inner.decode_blocks(gathered).reshape(-1)[:total]
    if c.stateful:
        c = codecs.NONE
    _account("all_gather", s.ledger_tag, chunk, axis, c, c, bwd_op=None,
             level=s.level or "flat")
    with _wire_site(s.ledger_tag):
        return _all_gather_flat_impl(chunk, axis, total, c)


def _all_gather_flat_impl(chunk, axis, total, c):
    n = axis_size(axis)
    if n == 1:
        return chunk[:total]
    if c.is_identity:
        _log("all_gather", "-", c, chunk.size * chunk.dtype.itemsize, n - 1)
        full = lax.all_gather(chunk, axis, axis=0, tiled=True)
    else:
        x2d = chunk.reshape(-1, BLOCK)
        wire = c.encode_blocks(x2d)
        _log("all_gather", "-", c, ops.wire_nbytes(wire), n - 1)
        gathered = jax.tree.map(
            lambda l: lax.all_gather(l, axis, axis=0, tiled=True), wire)
        full = c.decode_blocks(gathered).reshape(-1)
    return full[:total]


# ---- carried-state sum collectives (ef:* and plr*) -----------------------

def _lowrank_psum_impl(x, axis, c, state, want_local=False):
    """PowerSGD-shaped two-factor all-reduce (arXiv:1905.13727).

    Every rank holds the same warm factor ``Q`` (deterministic init, and
    both updates below are computed from all-reduced values):

        P   = allreduce_sum(M_i @ Q)        wire: m x r floats
        P^  = orth(P)                       local, identical on all ranks
        Q'  = allreduce_sum(M_i^T @ P^)     wire: n x r floats
        sum ~ P^ @ Q'^T                     = low-rank approx of sum(M_i)

    Returns ``(sum, state')`` — plus this rank's own reconstruction
    ``P^ @ (M_i^T P^)^T`` when ``want_local`` (the error-feedback wrapper
    needs the local transmitted approximation for its residual)."""
    from repro.kernels import lowrank
    n_ranks = axis_size(axis)
    flatx = x.reshape(-1).astype(jnp.float32)
    mat = lowrank.to_mat(flatx)
    q = state["q"]
    p = lowrank.matmul(mat, q, c.backend)
    if n_ranks > 1:
        p = lax.psum(p, axis)
    phat = lowrank.orthonormalize(p)
    q_loc = lowrank.matmul(mat.T, phat, c.backend)
    q_new = lax.psum(q_loc, axis) if n_ranks > 1 else q_loc
    out = lowrank.from_mat(lowrank.matmul(phat, q_new.T, c.backend),
                           flatx.shape[0])
    out = out.reshape(x.shape)
    state2 = {"q": lowrank.orthonormalize(q_new)}
    if want_local:
        rec = lowrank.from_mat(lowrank.matmul(phat, q_loc.T, c.backend),
                               flatx.shape[0]).reshape(x.shape)
        return out, state2, rec
    return out, state2


def _stateful_psum(x, axis, s, c):
    """All-reduce under a carried-state codec (optimizer-side, no VJP)."""
    io, key, st = _state_slot(s, c)
    if axis_size(axis) == 1:
        return x        # nothing crosses the wire; the slot carries over
    # accounting note: bwd_op matches what the stateless psum path records
    # at the same site, so stateful-vs-stateless byte comparisons at one
    # site (ef:bq4 vs raw bq4 — identical wires) stay apples-to-apples
    if c.kind == "lowrank":
        _account("all_reduce", s.ledger_tag, x, axis, c, c,
                 bwd_op="all_reduce", level=s.level or "flat")
        out, st2 = _lowrank_psum_impl(x, axis, c, st)
        io.write(key, st2)
        return out.astype(x.dtype)
    if c.kind != "ef":
        raise NotImplementedError(
            f"carried-state codec {c.name!r} (kind={c.kind!r}) has no "
            "sum-collective implementation in comms")
    # error feedback: compensate -> ride the inner codec -> stash residual
    xc = c.compensate(x, st)
    _account("all_reduce", s.ledger_tag, xc, axis, c, c,
             bwd_op="all_reduce", level=s.level or "flat")
    if c.inner.stateful:    # ef:plr* — PowerSGD with error feedback
        out, inner_st2, rec = _lowrank_psum_impl(xc, axis, c.inner,
                                                 st["inner"],
                                                 want_local=True)
        io.write(key, {"residual": xc - rec, "inner": inner_st2})
    else:
        io.write(key, c.next_state(xc))
        out = _psum_impl(xc, axis, c.inner)
    return out.astype(x.dtype)


def _stateful_reduce_scatter_flat(flat, axis, s, c, mean):
    io, key, st = _state_slot(s, c)
    n = axis_size(axis)
    chunk_len = ops.padded_rows(-(-flat.shape[0] // n)) * BLOCK

    def _take_chunk(total_vec):
        padded = jnp.pad(total_vec, (0, n * chunk_len - total_vec.shape[0]))
        chunk = lax.dynamic_index_in_dim(padded.reshape(n, chunk_len),
                                         lax.axis_index(axis), 0,
                                         keepdims=False)
        return chunk / n if mean else chunk

    if c.kind == "lowrank":
        # the low-rank op is inherently an all-reduce; RS = AR + local slice
        _account("all_reduce", s.ledger_tag, flat, axis, c, c, bwd_op=None,
                 level=s.level or "flat")
        total, st2 = _lowrank_psum_impl(flat, axis, c, st)
        io.write(key, st2)
        return _take_chunk(total)
    if c.kind != "ef":
        raise NotImplementedError(
            f"carried-state codec {c.name!r} (kind={c.kind!r}) has no "
            "reduce-scatter implementation in comms")
    xc = c.compensate(flat, st)
    if c.inner.stateful:    # ef:plr* — PowerSGD with error feedback
        _account("all_reduce", s.ledger_tag, xc, axis, c, c, bwd_op=None,
                 level=s.level or "flat")
        total, inner_st2, rec = _lowrank_psum_impl(xc, axis, c.inner,
                                                   st["inner"],
                                                   want_local=True)
        io.write(key, {"residual": xc - rec, "inner": inner_st2})
        return _take_chunk(total)
    _account("reduce_scatter", s.ledger_tag, xc, axis, c, c, bwd_op=None,
             level=s.level or "flat")
    io.write(key, c.next_state(xc))
    return _reduce_scatter_flat_impl(xc, axis, c.inner, mean)


def _stateful_hier_psum(x, inner, outer, s, c_in, c_out):
    """Two-level all-reduce with per-level carried-state codecs.

    Optimizer-side twin of :func:`_hier_psum_impl` — ``RS(inner) ->
    AR(outer) -> AG(inner)`` on the flattened payload, where each level's
    codec may carry state in its own level-pinned slot
    (``<dim>_inner@name`` / ``<dim>_outer@name``; the trainers enumerate
    per-level slots for hierarchical sync sites).  The stage-3 gather
    rides the inner TRANSPORT codec (an ``ef:*`` inner's wire codec):
    error feedback compensates the stage-1 reduction, and re-compensating
    the already-reduced chunks on the way back out would double-count the
    residual.  ``plr*`` at the inner level has no scatter/gather
    decomposition and raises — put low-rank codecs at the outer level
    (the slow links, where the factor wire wins).  Ledger: per-stage
    events at the level-pinned tags, mirroring :func:`hier_all_reduce`'s
    inner/outer attribution."""
    n_i, n_o = axis_size(inner), axis_size(outer)
    total = x.size
    flat = x.reshape(-1)
    s_in = policy.Site(s.dim, name=s.name, direction=s.direction,
                       level="inner")
    s_out = policy.Site(s.dim, name=s.name, direction=s.direction,
                        level="outer")
    # stage 1: intra-node reduce-scatter under the inner codec
    if n_i == 1:
        m = ops.padded_rows(total)
        chunk = jnp.pad(flat, (0, m * BLOCK - total))
    elif c_in.stateful:
        if c_in.kind == "lowrank" or (c_in.kind == "ef"
                                      and c_in.inner.stateful):
            raise NotImplementedError(
                f"codec {c_in.name!r} at the inner level of hier site "
                f"{s.ledger_tag!r}: low-rank codecs ride flat sum "
                "collectives only — route plr* to the outer level")
        with _wire_site(s_in.ledger_tag):
            chunk = _stateful_reduce_scatter_flat(flat, inner, s_in, c_in,
                                                  mean=False)
    else:
        _account("reduce_scatter", s_in.ledger_tag, flat, inner, c_in,
                 c_in, bwd_op=None, level="inner")
        with _wire_site(s_in.ledger_tag):
            chunk = _reduce_scatter_flat_impl(flat, inner, c_in, False)
    # stage 2: inter-node all-reduce of the 1/n_i chunk
    if n_o > 1:
        if c_out.stateful:
            with _wire_site(s_out.ledger_tag):
                chunk = _stateful_psum(chunk, outer, s_out, c_out)
        else:
            _account("all_reduce", s_out.ledger_tag, chunk, outer, c_out,
                     c_out, bwd_op=None, level="outer")
            with _wire_site(s_out.ledger_tag):
                chunk = _psum_impl(chunk, outer, c_out)
    # stage 3: intra-node all-gather of the fully-reduced chunks
    if n_i == 1:
        out = chunk[:total]
    else:
        c_t = c_in.inner if c_in.stateful else c_in
        _account("all_gather", s_in.ledger_tag, chunk, inner, c_t, c_t,
                 bwd_op=None, level="inner")
        with _wire_site(s_in.ledger_tag):
            out = _all_gather_flat_impl(chunk, inner, total, c_t)
    return out.reshape(x.shape).astype(x.dtype)


# --------------------------------------------------------------------------
# runtime-tunable sites: lax.switch over the executable rungs of the codec
# ladder.  The self-tuning controller (repro.tune) changes a site's codec
# by feeding a different rung index into the next step's tune_state — an
# integer swap, not a retrace: the switch carries every rung's lowering in
# the one compiled executable.
# --------------------------------------------------------------------------

def _tuned_psum(x, axis, s, c_plan):
    return _tuned_collective(x, axis, s, c_plan, "ar")


def _tuned_reduce_scatter_flat(flat, axis, s, c_plan, mean):
    return _tuned_collective(flat, axis, s, c_plan, "rs", mean)


def _tuned_collective(x, axis, s, c_plan, kind, mean=False):
    """Sum collective dispatched at runtime over the tuning ladder rungs.

    Branch order MUST match :data:`repro.tune.ladder.RUNGS` —
    ``(bq16, bq8, ef:bq4, plr2, plr4, plr8)``.  Every branch returns the
    same pytree ``(out, residual', q', sig)`` so ``lax.switch`` unifies:
    the union codec state (an EF residual AND a warm low-rank factor,
    held in the site's ``codec_state_io`` slot) is threaded through all
    rungs, with inactive parts passed through unchanged.

    Signals (:mod:`repro.tune.tracker` layout): every rung measures the
    payload energy and a squared compression error — its OWN realized
    error for ``ef``/``plr`` rungs, a local next-rung roundtrip probe for
    the ``bq`` rungs (so the controller's promote test reads the error
    the next rung WOULD take, before committing traffic to it).  The
    ``ef:bq4`` and ``plr`` rungs additionally run one full-width
    power-iteration probe of the warm factor: ``orthonormalize`` is
    column-sequential Gram-Schmidt, so the leading-``r`` slice of the
    full-rank iteration is EXACTLY the ``plr<r>`` iteration — one probe
    prices every registered rank, and a promotion into ``plr`` enters
    with a converged factor and a measured spectrum.

    Ledger: the switch traces all rungs, so per-branch events are muted
    and ONE analytic event is recorded, priced at the plan's static
    resolution (``c_plan``, the startup codec) with a ``tunable=1`` fact
    — recorded-bytes comparisons read the measured decision history, not
    the static event stream."""
    from repro.kernels import lowrank
    from repro.tune import ladder as _ladder
    from repro.tune import tracker as _tracker
    tio = _tune.io
    key = s.ledger_tag
    cio = getattr(_state, "io", None)
    if cio is None:
        raise RuntimeError(
            f"tunable site {key!r} traced outside a codec_state_io region "
            "— tunable sites carry a union codec-state slot; wrap the "
            "optimizer sync in comms.codec_state_io(...)")
    st = cio.read(key)
    n = axis_size(axis)
    f32 = x.reshape(-1).astype(jnp.float32)
    payload_sq = jnp.sum(f32 * f32)
    q0 = st["q"]
    R = q0.shape[-1]
    chunk_len = ops.padded_rows(-(-f32.shape[0] // n)) * BLOCK

    def _take_chunk(total_vec):
        padded = jnp.pad(total_vec, (0, n * chunk_len - total_vec.shape[0]))
        chunk = lax.dynamic_index_in_dim(padded.reshape(n, chunk_len),
                                         lax.axis_index(axis), 0,
                                         keepdims=False)
        return chunk / n if mean else chunk

    def _blocks(v):
        m = ops.padded_rows(v.shape[0])
        return jnp.pad(v, (0, m * BLOCK - v.shape[0])).reshape(-1, BLOCK)

    def _probe_err(v, probe):
        x2d = _blocks(v)
        dec = probe.decode_blocks(probe.encode_blocks(x2d))
        return jnp.sum((x2d - dec) ** 2)

    def _power_iter(mat, q):
        p = lowrank.matmul(mat, q, None)
        if n > 1:
            p = lax.psum(p, axis)
        phat = lowrank.orthonormalize(p)
        q_loc = lowrank.matmul(mat.T, phat, None)
        q_new = lax.psum(q_loc, axis) if n > 1 else q_loc
        spec = jnp.pad(jnp.sum(p * p, axis=0),
                       (0, _ladder.PLR_MAX_RANK - R))
        return phat, q_loc, q_new, spec

    def _ride(v, c):
        if kind == "rs":
            return _reduce_scatter_flat_impl(v, axis, c, mean)
        return _psum_impl(v, axis, c)

    bq16, bq8, bq4 = codecs.get("bq16"), codecs.get("bq8"), codecs.get("bq4")

    def _bq_rung(c, probe):
        def branch(v, residual, q):
            sig = _tracker.pack(1.0, payload_sq, _probe_err(v, probe), None)
            return _ride(v, c), residual, q, sig
        return branch

    def _ef4_rung(v, residual, q):
        xc = v + residual
        x2d = _blocks(xc)
        dec = bq4.decode_blocks(bq4.encode_blocks(x2d))
        new_res = (x2d - dec).reshape(-1)[:v.shape[0]]
        mat = lowrank.to_mat(xc)
        _, _, q_new, spec = _power_iter(mat, q)
        sig = _tracker.pack(1.0, payload_sq, jnp.sum(new_res * new_res),
                            spec)
        return _ride(xc, bq4), new_res, lowrank.orthonormalize(q_new), sig

    def _plr_rung(r):
        r_eff = min(r, R)

        def branch(v, residual, q):
            mat = lowrank.to_mat(v)
            phat, q_loc, q_new, spec = _power_iter(mat, q)
            total = lowrank.from_mat(
                lowrank.matmul(phat[:, :r_eff], q_new[:, :r_eff].T, None),
                v.shape[0])
            rec = lowrank.matmul(phat[:, :r_eff], q_loc[:, :r_eff].T, None)
            sig = _tracker.pack(1.0, payload_sq, jnp.sum((mat - rec) ** 2),
                                spec)
            out = _take_chunk(total) if kind == "rs" else total
            return out, residual, lowrank.orthonormalize(q_new), sig
        return branch

    branches = [_bq_rung(bq16, bq8), _bq_rung(bq8, bq4), _ef4_rung,
                _plr_rung(2), _plr_rung(4), _plr_rung(8)]
    assert len(branches) == len(_ladder.RUNGS)
    op = "reduce_scatter" if kind == "rs" else "all_reduce"
    with scope_facts(tunable=1):
        _account(op, key, x, axis, c_plan, c_plan, bwd_op=None,
                 level=s.level or "flat")
    with mute_ledger():
        sel = jnp.asarray(tio.select[key], jnp.int32)
        out, new_res, new_q, sig = lax.switch(
            sel, branches, f32, st["residual"], q0)
    cio.write(key, {"residual": new_res, "q": new_q})
    tio.add_sig(key, sig)
    if kind == "ar":
        out = out.reshape(x.shape)
    return out.astype(x.dtype)
