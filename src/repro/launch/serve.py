"""Serving entrypoint: batched, paged-continuous, and disaggregated modes.

On the CPU the mesh runs on host devices, which JAX gives only to a run
with ``JAX_PLATFORMS=cpu``; on an accelerator it takes the devices it has.

    # classic batched prefill + greedy decode
    JAX_PLATFORMS=cpu PYTHONPATH=src python -m repro.launch.serve \
        --arch gemma3-1b --reduced \
        --dp 2 --tp 4 --batch 4 --prompt-len 16 --gen 8 --scheme baseline

    # continuous batching over a paged KV pool, quantized at rest
    JAX_PLATFORMS=cpu PYTHONPATH=src python -m repro.launch.serve \
        --arch gemma3-1b --reduced \
        --mode paged --kv-codec bq8 --slots 4 --batch 8 --gen 8

    # prefill/decode disaggregation with a compressed KV handoff
    JAX_PLATFORMS=cpu PYTHONPATH=src python -m repro.launch.serve \
        --arch gemma3-1b --reduced \
        --mode disagg --dp 2 --tp 2 --kv-codec bq16 --batch 4 --gen 8

The policy flags (--scheme / --codec-for / --no-compress-below) and ring
knobs (--ring-bidir / --ring-chunks) match repro.launch.train — a named
scheme is sugar over rules, CLI overrides prepend first-match-wins rules,
and the ``kv`` dimension routes the serving-only traffic (pool handoff,
at-rest page codec).
"""

from __future__ import annotations

import argparse
import time

from repro.launch import runtime
from repro.launch.train import comm_policy_from_flags


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--mode", choices=("batched", "paged", "disagg"),
                    default="batched",
                    help="batched: dense prefill+decode; paged: continuous "
                         "batching over a paged KV pool; disagg: prefill/"
                         "decode pools with a compressed KV handoff "
                         "(needs 2*dp*tp devices)")
    ap.add_argument("--dp", type=int, default=1)
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--batch", type=int, default=4,
                    help="requests (batched/disagg: batch size; paged: "
                         "total submitted requests)")
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=8)
    ap.add_argument("--max-len", type=int, default=0)
    ap.add_argument("--scheme", default="baseline")
    ap.add_argument("--kv-codec", default="none",
                    help="paged: at-rest storage codec of the KV pool "
                         "(none | bq4/bq8/bq16/bq24); disagg: wire codec "
                         "of the prefill->decode handoff (any codec)")
    ap.add_argument("--block-tokens", type=int, default=16,
                    help="paged-mode KV block size in tokens")
    ap.add_argument("--slots", type=int, default=4,
                    help="paged-mode concurrent decode slots")
    ap.add_argument("--kv-blocks", type=int, default=0,
                    help="paged-mode global pool blocks (0 = sized to fit "
                         "all slots at max context)")
    ap.add_argument("--no-compress-below", type=int, default=0,
                    metavar="BYTES",
                    help="policy rule: payloads smaller than BYTES ride "
                         "uncompressed (latency-bound small collectives "
                         "gain nothing from encode/decode)")
    ap.add_argument("--codec-for", action="append", default=[],
                    metavar="[DIM@]NAME_GLOB=CODEC",
                    help="policy rule: override the codec for comm sites "
                         "whose name matches the glob, optionally pinned "
                         "to one parallelism dimension (repeatable; e.g. "
                         "attn*=bq16, kv@prefill*=bq8, kv=bq16)")
    ap.add_argument("--ring-bidir", action="store_true",
                    help="split compressed ring collectives into two "
                         "counter-rotating half-rings (halves per-link "
                         "bytes; falls back to one ring, visibly in the "
                         "ledger, when the payload is under a tile per "
                         "direction)")
    ap.add_argument("--ring-chunks", type=int, default=1,
                    help="stripe each compressed ring collective into N "
                         "independently-pipelined row chunks so chunk "
                         "k+1's encode overlaps chunk k's transfer")
    ap.add_argument("--tp-nodes", default="1",
                    help="factor tp into (tpnode, model) sub-axes; the "
                         "serve-path TP/EP collectives run two-level")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    n_dev = args.dp * args.tp * (2 if args.mode == "disagg" else 1)
    runtime.force_cpu_devices(n_dev)
    runtime.use_compile_cache()
    runtime.require_devices(n_dev)

    import numpy as np

    from repro import configs

    cfg = configs.get(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    try:
        comm_policy = comm_policy_from_flags(args)
    except ValueError as e:
        ap.error(str(e))
    rng = np.random.default_rng(args.seed)
    B, S = args.batch, args.prompt_len
    prompts = rng.integers(0, cfg.vocab_size, (B, S)).astype(np.int32)

    if args.mode == "paged":
        _run_paged(args, cfg, comm_policy, prompts)
    elif args.mode == "disagg":
        _run_disagg(args, cfg, comm_policy, prompts)
    else:
        _run_batched(args, cfg, comm_policy, prompts)


def _make_model(args, cfg, dp, tp):
    import jax

    from repro.launch.mesh import make_mesh, parse_nodes_spec
    from repro.models.model import Model
    from repro.models.params import MeshInfo

    tp_nodes = parse_nodes_spec(args.tp_nodes, tp, flag="--tp-nodes")
    mesh = make_mesh(dp, tp, tp_nodes=tp_nodes)
    mi = MeshInfo.from_mesh(mesh)
    model = Model(cfg, mi)
    params = model.init(jax.random.key(args.seed))
    return mesh, mi, model, params


def _run_batched(args, cfg, comm_policy, prompts):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.serve import kv_cache
    from repro.serve.serve_step import Server
    from repro.train.train_step import batch_specs

    mesh, mi, model, params = _make_model(args, cfg, args.dp, args.tp)
    srv = Server(model, mesh, scheme=comm_policy,
                 ring_bidir=args.ring_bidir, ring_chunks=args.ring_chunks)

    B, S = prompts.shape
    s_max = args.max_len or (-(-(S + args.gen) // (2 * args.tp))
                             * (2 * args.tp))
    bspecs = batch_specs(cfg, mi)
    batch = {"tokens": jax.device_put(
        jnp.asarray(prompts), NamedSharding(mesh, bspecs["tokens"])),
        "labels": jax.device_put(
        jnp.asarray(prompts), NamedSharding(mesh, bspecs["labels"]))}
    if cfg.encoder_layers:
        frames = np.random.default_rng(args.seed).normal(
            size=(B, S, cfg.d_model)).astype(np.float32)
        batch["frames"] = jax.device_put(
            jnp.asarray(frames), NamedSharding(mesh, bspecs["frames"]))

    t0 = time.time()
    prefill = srv.prefill_step({k: bspecs[k] for k in batch}, B)
    tok, caches = prefill(params, batch)
    print(f"prefill[{B}x{S}] {time.time() - t0:.2f}s "
          f"-> first tokens {np.asarray(tok)[:4]}")

    # pad prefill caches into the decode layout
    structs, cspecs = kv_cache.cache_structs(cfg, mi, B, s_max, ("model",),
                                             s_enc=S)
    padded = []
    for st, cs, pc in zip(structs, cspecs, caches):
        if st is None:
            padded.append(None)
            continue
        new = {}
        for k, v in st.items():
            if k == "xlen":
                new[k] = jax.device_put(jnp.full(v.shape, S, jnp.int32),
                                        NamedSharding(mesh, cs[k]))
                continue
            a = np.zeros(v.shape, v.dtype)
            if pc is not None and k in pc:
                s = np.asarray(pc[k])
                a[tuple(slice(0, d) for d in s.shape)] = s
            new[k] = jax.device_put(jnp.asarray(a),
                                    NamedSharding(mesh, cs[k]))
        padded.append(new)

    dec, _, _ = srv.decode_step(B, s_max, s_enc=S)
    out = [np.asarray(tok)]
    caches = padded
    t0 = time.time()
    for i in range(1, args.gen):
        tok_in = jax.device_put(
            jnp.asarray(out[-1])[:, None],
            NamedSharding(mesh, P(mi.batch_axes if B > 1 else None, None)))
        tok, caches = dec(params, tok_in, caches, jnp.int32(S + i - 1))
        out.append(np.asarray(tok))
    dt = time.time() - t0
    gen = np.stack(out, 1)
    print(f"decoded {args.gen - 1} steps in {dt:.2f}s "
          f"({(args.gen - 1) * B / max(dt, 1e-9):.1f} tok/s)")
    for b in range(min(B, 4)):
        print(f"  seq[{b}]: {prompts[b, -4:].tolist()} -> {gen[b].tolist()}")


def _run_paged(args, cfg, comm_policy, prompts):
    from repro.serve import paged_kv
    from repro.serve.scheduler import Scheduler
    from repro.serve.serve_step import PagedServer

    mesh, mi, model, params = _make_model(args, cfg, args.dp, args.tp)
    B, S = prompts.shape
    bt = args.block_tokens
    max_blocks = paged_kv.blocks_needed(S + args.gen, bt)
    n_slots = max(args.slots, mi.batch_ways)
    n_blocks = args.kv_blocks or n_slots * max_blocks
    srv = PagedServer(model, mesh, scheme=comm_policy,
                      kv_codec=args.kv_codec, block_tokens=bt,
                      ring_bidir=args.ring_bidir,
                      ring_chunks=args.ring_chunks)
    step, structs, _ = srv.decode_step(n_slots, n_blocks, max_blocks)
    pool = paged_kv.zero_pool(structs)
    sched = Scheduler(n_slots, n_blocks, bt, max_blocks, dp=mi.batch_ways)
    for b in range(B):
        sched.submit(b, prompts[b].tolist(), args.gen)
    t0 = time.time()
    finished, pool, steps = sched.run(step, params, pool)
    dt = time.time() - t0
    total = sum(len(v) for v in finished.values())
    print(f"paged[{args.kv_codec}] {B} requests ({S}+{args.gen} tokens) on "
          f"{n_slots} slots x {n_blocks} blocks: {steps} steps, {dt:.2f}s "
          f"({total / max(dt, 1e-9):.1f} gen tok/s)")
    for b in range(min(B, 4)):
        print(f"  req[{b}]: {prompts[b, -4:].tolist()} -> {finished[b]}")


def _run_disagg(args, cfg, comm_policy, prompts):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.analysis import roofline
    from repro.core import comms
    from repro.models.model import Model
    from repro.models.params import MeshInfo
    from repro.serve.disagg import DECODE, DisaggServer, make_disagg_mesh
    from repro.train.train_step import batch_specs

    mesh = make_disagg_mesh(args.dp, args.tp)
    mi = MeshInfo.from_mesh(mesh)
    model = Model(cfg, mi)
    params = model.init(jax.random.key(args.seed))
    srv = DisaggServer(model, mesh, scheme=comm_policy,
                       kv_codec=args.kv_codec, ring_bidir=args.ring_bidir,
                       ring_chunks=args.ring_chunks)
    B, S = prompts.shape
    s_max = args.max_len or (-(-(S + args.gen) // (2 * args.tp))
                             * (2 * args.tp))
    bspecs = batch_specs(cfg, mi)
    staged = srv.stage_batch({"tokens": prompts, "labels": prompts}, bspecs)

    t0 = time.time()
    prefill = srv.prefill_step({k: bspecs[k] for k in staged}, B)
    tok0, caches = prefill(params, staged)
    print(f"prefill pool [{B}x{S}] {time.time() - t0:.2f}s")

    padded = srv.pad_prefill_caches(jax.tree.map(np.asarray, caches), B,
                                    s_max)
    hand = srv.handoff_step(B, s_max)
    with comms.record_traffic() as events:
        padded = hand(padded)
        jax.block_until_ready(padded)
    evs = list(events)
    byt = sum(roofline.event_bytes(e, train=False)["fwd"] for e in evs)
    secs = roofline.kv_handoff_seconds(evs)
    print(f"kv handoff [{args.kv_codec}]: {len(evs)} transfers, "
          f"{byt / 1e6:.2f} MB/device wire, {secs * 1e3:.2f} ms analytic")

    dec = srv.decode_step(B, s_max)
    out = [np.asarray(tok0)[0]]          # prefill pool's first token
    t0 = time.time()
    for i in range(1, args.gen):
        g = np.zeros((2, B, 1), np.int32)
        g[DECODE] = out[-1][:, None]
        tok_in = jax.device_put(
            jnp.asarray(g),
            NamedSharding(mesh, P("pool",
                                  None if B == 1 else mi.batch_axes, None)))
        t, padded = dec(params, tok_in, padded, jnp.int32(S + i - 1))
        out.append(np.asarray(t)[DECODE])
    dt = time.time() - t0
    gen = np.stack(out, 1)
    print(f"decode pool: {args.gen - 1} steps in {dt:.2f}s "
          f"({(args.gen - 1) * B / max(dt, 1e-9):.1f} tok/s)")
    for b in range(min(B, 4)):
        print(f"  seq[{b}]: {prompts[b, -4:].tolist()} -> {gen[b].tolist()}")


if __name__ == "__main__":
    main()
