"""Ledger completeness: every compressed collective entry point must put
its REAL wire bytes in the measured ledger, and the analytic event the
roofline prices must agree with them exactly.

For each (codec x axis size x entry point) cell:

  * run the collective under ``comms.record_traffic``;
  * assert the measured wire events (``events.wire``) carry exactly
    ``codec.wire_nbytes_for(padded elems) x hops`` — tile padding
    included, per the wire-format contract (this is what caught gq/tq
    pricing their per-row broadcast scale at zero bytes);
  * assert the analytic event stream prices to the SAME total via
    ``roofline.event_bytes`` (block-codec geometry pricing), so
    ``--suggest --from-ledger`` can never drift from what actually ran;
  * assert the realized ring schedule is visible: bidirectional split
    facts (parts/bidir) when realized, ``fallback=True`` when the
    half-tile floor rejects a requested split (satellite: the silent
    ``(m//2)//8*8 < 8`` fallback used to be invisible).

Stateful codecs (``ef:*``/``plr*``) are excluded: their psum path is
optimizer-only (inside ``codec_state_io``) and is ledger-tested by
test_codec_state.py.
"""

import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np, jax, jax.numpy as jnp  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.analysis import roofline as rl  # noqa: E402
from repro.core import codecs, comms, compat, policy as policy_lib  # noqa: E402
from repro.kernels import ops  # noqa: E402

BLOCK = 128
BLOCK_CODECS = ("bq4", "bq8", "bq16", "gq8", "tq8")
IDENTITY_CODECS = ("none", "mpc")

mesh8 = compat.make_mesh((8,), ("x",))
mesh24 = compat.make_mesh((2, 4), ("a", "b"))
rng = np.random.default_rng(0)


def run_one(mesh, axis, fn, shape):
    """Trace+run ``fn`` shard-mapped over every mesh axis; return the
    recorded (analytic events, wire events)."""
    spec = P(*mesh.axis_names)
    x = jnp.asarray(rng.normal(size=shape).astype(np.float32))
    sm = jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=(spec,),
                               out_specs=spec, check_vma=False))
    with comms.record_traffic() as events:
        jax.block_until_ready(sm(x))
    return list(events), list(events.wire)


def wire_total(wires):
    return sum(w["payload_bytes"] * w["hops"] for w in wires)


def chunk_wire(c, elems):
    """Analytic per-hop wire bytes of a ring whose per-rank chunk holds
    ``elems`` values (tile-padded, the wire-format contract)."""
    return c.wire_nbytes_for(ops.padded_rows(elems) * BLOCK)


def priced_total(events):
    """What ``--suggest --from-ledger`` would price these events at."""
    return sum(rl.event_bytes(ev, train=False)["fwd"] for ev in events)


def close(a, b, what):
    assert abs(a - b) < 1e-6, (what, a, b)


def check_cell(mesh, axis, n, codec_name, per_shape):
    c = codecs.get(codec_name)
    pol = policy_lib.CommPolicy(name=f"lc_{codec_name}",
                                rules=(policy_lib.Rule(codec_name),))
    plan = pol.compile(None)

    def wrap(body):
        def f(a):
            with policy_lib.use_plan(plan):
                return body(a)
        return f

    elems = 1
    for d in per_shape:
        elems *= d
    # global input shape: one leading dim per mesh axis
    gshape = tuple(mesh.shape[a] for a in mesh.axis_names) + per_shape
    dims = len(mesh.axis_names)
    ax_dim = dims  # first payload dim, divisible by every n we use

    # ---- psum: ring RS hops + all-gather of the final compressed chunk
    events, wires = run_one(mesh, axis, wrap(
        lambda a: comms.psum(a, axis, "dp")), gshape)
    hop = chunk_wire(c, -(-elems // n))
    assert [w["op"] for w in wires] == ["rs_ring", "ar_allgather"], wires
    close(wires[0]["payload_bytes"], hop, (codec_name, n, "psum rs hop"))
    close(wires[1]["payload_bytes"], hop, (codec_name, n, "psum ag hop"))
    assert wires[0]["hops"] == wires[1]["hops"] == n - 1
    close(wire_total(wires), 2 * (n - 1) * hop, (codec_name, n, "psum"))
    # the wire events carry the realized schedule next to the bytes
    assert wires[0]["parts"] == 1 and wires[0]["bidir"] is False
    assert wires[0]["fallback"] is False
    # the analytic event prices to the same total
    [ev] = [e for e in events if e["op"] == "all_reduce"]
    assert ev["ring"]["hops"] == n - 1 and ev["ring"]["fallback"] is False
    close(priced_total([ev]), wire_total(wires), (codec_name, n, "psum rl"))

    # ---- reduce_scatter: ring only (no re-encode on the final hop)
    events, wires = run_one(mesh, axis, wrap(
        lambda a: comms.reduce_scatter(a, axis, ax_dim, "dp")), gshape)
    hop = chunk_wire(c, elems // n)
    assert [w["op"] for w in wires] == ["rs_ring"], wires
    close(wire_total(wires), (n - 1) * hop, (codec_name, n, "rs"))
    [ev] = [e for e in events if e["op"] == "reduce_scatter"]
    close(priced_total([ev]), wire_total(wires), (codec_name, n, "rs rl"))

    # ---- all_gather: one encode, n-1 hops of the full local wire
    events, wires = run_one(mesh, axis, wrap(
        lambda a: comms.all_gather(a, axis, ax_dim, "dp")), gshape)
    full = chunk_wire(c, elems)
    assert [w["op"] for w in wires] == ["all_gather"], wires
    close(wire_total(wires), (n - 1) * full, (codec_name, n, "ag"))
    [ev] = [e for e in events if e["op"] == "all_gather"]
    close(priced_total([ev]), wire_total(wires), (codec_name, n, "ag rl"))

    # ---- ppermute (full ring): one hop of the full local wire
    perm = [(i, (i + 1) % n) for i in range(n)]
    events, wires = run_one(mesh, axis, wrap(
        lambda a: comms.ppermute(a, axis, perm, "pp")), gshape)
    assert [w["op"] for w in wires] == ["ppermute"], wires
    close(wire_total(wires), full, (codec_name, n, "ppermute"))

    # ---- all_to_all: n encoded slices, (n-1)/n of them cross the link
    events, wires = run_one(mesh, axis, wrap(
        lambda a: comms.all_to_all(a, axis, ax_dim, ax_dim, "ep")), gshape)
    slice_w = chunk_wire(c, elems // n)
    assert [w["op"] for w in wires] == ["all_to_all"], wires
    close(wire_total(wires), int(n * slice_w) * (n - 1) // n,
          (codec_name, n, "a2a"))


def check_identity(mesh, axis, n, codec_name, per_shape):
    """Identity-wire codecs (none/mpc) log raw payload bytes."""
    pol = policy_lib.CommPolicy(name=f"li_{codec_name}",
                                rules=(policy_lib.Rule(codec_name),))
    plan = pol.compile(None)

    def wrap(body):
        def f(a):
            with policy_lib.use_plan(plan):
                return body(a)
        return f

    elems = 1
    for d in per_shape:
        elems *= d
    nb = elems * 4
    gshape = tuple(mesh.shape[a] for a in mesh.axis_names) + per_shape
    ax_dim = len(mesh.axis_names)

    _, wires = run_one(mesh, axis, wrap(
        lambda a: comms.psum(a, axis, "dp")), gshape)
    close(wire_total(wires), 2 * nb, (codec_name, n, "psum"))
    _, wires = run_one(mesh, axis, wrap(
        lambda a: comms.reduce_scatter(a, axis, ax_dim, "dp")), gshape)
    close(wire_total(wires), nb, (codec_name, n, "rs"))
    _, wires = run_one(mesh, axis, wrap(
        lambda a: comms.all_gather(a, axis, ax_dim, "dp")), gshape)
    close(wire_total(wires), (n - 1) * nb, (codec_name, n, "ag"))


def check_ring_visibility():
    """Realized-vs-requested ring schedule must be readable off the event."""
    c = codecs.get("bq8")
    pol = policy_lib.CommPolicy(name="lc_vis",
                                rules=(policy_lib.Rule("bq8"),))
    plan = pol.compile(None)

    def psum_with(bidir, chunks):
        def f(a):
            with policy_lib.use_plan(plan), \
                    comms.ring_options(bidir, chunks):
                return comms.psum(a, "x", "dp")
        return f

    # small payload: 4096/8 -> 8-row chunk, an asked-for split can't keep
    # tile alignment -> fallback, full-price ring, and BOTH ledgers say so
    events, wires = run_one(mesh8, "x", psum_with(True, 1), (8, 4096))
    assert wires[0]["fallback"] is True and wires[0]["bidir"] is False
    assert wires[0]["parts"] == 1
    [ev] = [e for e in events if e["op"] == "all_reduce"]
    assert ev["bidir"] is True  # requested...
    assert ev["ring"]["fallback"] is True  # ...not realized, and visible
    close(wires[0]["payload_bytes"], chunk_wire(c, 512), "fallback hop")

    # big payload: the split is realized; the two half-rings carry the
    # same total bytes (row-striping is linear in rows for block codecs)
    events, wires = run_one(mesh8, "x", psum_with(True, 1), (8, 1 << 18))
    assert wires[0]["bidir"] is True and wires[0]["fallback"] is False
    assert wires[0]["parts"] == 2
    close(wires[0]["payload_bytes"], chunk_wire(c, (1 << 18) // 8),
          "bidir hop total")
    [ev] = [e for e in events if e["op"] == "all_reduce"]
    assert ev["ring"]["bidir"] is True and len(ev["ring"]["parts"]) == 2
    # roofline halves the per-link price only because the event says the
    # split was realized
    close(priced_total([ev]), wire_total(wires) * 0.5, "bidir rl price")

    # chunk striping: sub-rings are visible as extra parts, same bytes
    events, wires = run_one(mesh8, "x", psum_with(True, 2), (8, 1 << 18))
    assert wires[0]["parts"] == 4  # 2 directions x 2 chunk stripes
    close(wires[0]["payload_bytes"], chunk_wire(c, (1 << 18) // 8),
          "chunked hop total")
    [ev] = [e for e in events if e["op"] == "all_reduce"]
    assert ev["ring"]["chunks"] == 2


def main():
    cells = 0
    for mesh, axis, n in ((mesh8, "x", 8), (mesh24, "a", 2),
                          (mesh24, "b", 4)):
        for name in BLOCK_CODECS:
            # both tile-aligned and ragged payloads; dim0 divisible by 8
            for per_shape in ((32, 256), (24, 37)):
                check_cell(mesh, axis, n, name, per_shape)
                cells += 1
        for name in IDENTITY_CODECS:
            check_identity(mesh, axis, n, name, (32, 256))
            cells += 1
        print(f"axis size {n}: ledger complete "
              f"({len(BLOCK_CODECS)} block + {len(IDENTITY_CODECS)} "
              "identity codecs x 5 entry points)")
    check_ring_visibility()
    print("ring schedule visibility (bidir/fallback/chunks) OK")
    print(f"LEDGER COMPLETENESS OK ({cells} cells)")


if __name__ == "__main__":
    main()
