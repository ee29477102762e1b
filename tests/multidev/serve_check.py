"""Serving correctness: prefill+decode continuation must equal repeated
teacher-forced forward argmax (cache equivalence), on a (2,4) mesh."""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro import configs
from repro.models.model import Model
from repro.models.params import MeshInfo
from repro.serve.serve_step import Server
from repro.serve import kv_cache
from repro.train.train_step import batch_specs
from repro.core import compat, schemes

mesh = compat.make_mesh((2, 4), ("data", "model"))
mi = MeshInfo.from_mesh(mesh)
rng = np.random.default_rng(0)

def put(x, spec):
    return jax.device_put(x, NamedSharding(mesh, spec))

def run_arch(arch, S=16, B=4, n_new=4, s_max=32):
    cfg = configs.get(arch).reduced()
    model = Model(cfg, mi)
    params = model.init(jax.random.key(7))
    srv = Server(model, mesh, scheme="baseline")
    toks = rng.integers(0, cfg.vocab_size, (B, S)).astype(np.int32)
    batch = {"tokens": put(jnp.asarray(toks), P("data", None)),
             "labels": put(jnp.asarray(toks), P("data", None))}
    bspecs = batch_specs(cfg, mi)
    if cfg.encoder_layers:
        frames = rng.normal(size=(B, S, cfg.d_model)).astype(np.float32)
        batch["frames"] = put(jnp.asarray(frames), bspecs["frames"])

    # reference: teacher-forced argmax continuation via full re-forward
    def ref_logits(tokens_np):
        b2 = dict(batch)
        b2["tokens"] = put(jnp.asarray(tokens_np), P("data", None))
        b2["labels"] = b2["tokens"]
        def f(p, bb):
            with schemes.use("baseline"):
                logits, _, _ = model.forward(p, bb, phase="train")
            return logits  # [B, S_full, V_loc] on each model shard
        sm = jax.jit(jax.shard_map(f, mesh=mesh,
                     in_specs=(model.specs(), {k: bspecs[k] for k in b2}),
                     out_specs=P("data", None, "model"), check_vma=False))
        return np.asarray(sm(params, b2))  # [B, S_full, V]

    ref_toks = []
    cur = toks.copy()
    for i in range(n_new):
        L = cur.shape[1]
        Lp = -(-L // 4) * 4  # pad seq to a multiple of tp
        cur_p = np.concatenate([cur, np.zeros((B, Lp - L), np.int32)], 1)
        lg = ref_logits(cur_p)
        nxt = lg[:, L - 1, :cfg.vocab_size].argmax(-1).astype(np.int32)
        ref_toks.append(nxt)
        cur = np.concatenate([cur, nxt[:, None]], 1)

    # serve path: prefill then decode
    prefill = srv.prefill_step(bspecs if not cfg.encoder_layers else
                               {k: bspecs[k] for k in batch}, B)
    tok0, caches = prefill(params, batch)
    tok0 = np.asarray(tok0)
    # pad caches to s_max and install xlen for enc-dec
    structs, cspecs = kv_cache.cache_structs(cfg, mi, B, s_max, ("model",), s_enc=S)
    padded = []
    for st, cs, pc, g in zip(structs, cspecs, caches, cfg.layer_groups):
        if st is None or pc is None:
            padded.append(kv_cache.zero_caches(st) if st is not None else None)
            continue
        new = {}
        for k, v in st.items():
            if k == "xlen":
                new[k] = put(jnp.full(v.shape, S, jnp.int32), cs[k]); continue
            src = pc[k] if k in pc else None
            a = np.zeros(v.shape, v.dtype)
            s = np.asarray(src)
            sl = tuple(slice(0, d) for d in s.shape)
            a[sl] = s
            new[k] = put(jnp.asarray(a), cs[k])
        padded.append(new)
    dec, _, _ = srv.decode_step(B, s_max, s_enc=S)
    got = [tok0]
    tok = tok0
    caches = padded
    for i in range(1, n_new):
        tok_in = put(jnp.asarray(tok)[:, None], P("data", None))
        tok, caches = dec(params, tok_in, caches, jnp.int32(S + i - 1))
        tok = np.asarray(tok)
        got.append(tok)
    got = np.stack(got, 1); ref = np.stack(ref_toks, 1)
    match = (got == ref).mean()
    print(f"{arch:22s} decode-match={match:.2f} ref={ref[0]} got={got[0]}")
    return match

ok = True
# attention caches must match exactly; recurrent paths (chunked prefill vs
# sequential decode) differ by f32 rounding, which can flip near-tied
# argmaxes on a random-init model -> relaxed threshold.
for arch, thr in (("gemma3-1b", 1.0), ("qwen2-72b", 1.0),
                  ("whisper-base", 1.0), ("zamba2-1.2b", 0.75),
                  ("xlstm-1.3b", 0.75)):
    m = run_arch(arch)
    ok &= (m >= thr)
assert ok, "decode mismatch"
print("SERVE DECODE OK")
