"""Batched serving: prefill a batch of prompts, then step the decoder with
a KV cache (windowed / recurrent state depending on architecture).

    PYTHONPATH=src python examples/serve_decode.py --arch h2o-danube-3-4b

``--het-tier`` instead serves decode-step matvecs (the
``decode_gemv`` suite kernel) through the hetGPU multi-tenant serving
tier: weighted tenants on sticky streams, quota-based admission,
pooled buffers, async D2H of each result:

    PYTHONPATH=src python examples/serve_decode.py --het-tier

``--zoo`` decodes a tiny model whose attention is the model-zoo
``attn_decode`` kernel (repro.zoo) running on the hetGPU runtime: every
token's flash-decode launch goes through the serving tier, one token's
launch is checkpointed *mid-softmax* and live-migrated
interp -> vectorized -> pallas across a shared cache fabric, and every
token's logits are asserted bit-identical to the NumPy oracle:

    PYTHONPATH=src python examples/serve_decode.py --zoo
"""
import argparse
import time

import numpy as np
from repro.device import configure_compile_cache


def het_tier(requests_per_tenant: int = 24) -> None:
    """Multi-tenant decode serving on the hetGPU runtime (no jax)."""
    from repro.core import HetSession, ServingFrontEnd, TranslationCache
    from repro.core import kernels_suite as suite

    GRID, BLOCK, K, KTILES = 4, 16, 32, 4   # 64 output rows, 4 segments
    M = GRID * BLOCK
    prog, oracle = suite.decode_gemv()
    s = HetSession("vectorized", cache=TranslationCache())
    fn = s.load(prog).function()
    front = ServingFrontEnd(s, default_quota=8, slo_ms=1000.0)
    tenants = {"bronze": 1.0, "silver": 2.0, "gold": 4.0}
    for name, w in tenants.items():
        front.tenant(name, weight=w)

    rng = np.random.default_rng(7)
    W = s.alloc(M * K).copy_from_host(
        rng.normal(size=M * K).astype(np.float32) * 0.1)   # shared weights
    wx, wr, wo = s.alloc(K), s.alloc(M), s.alloc(M)
    fn.launch(GRID, BLOCK, {"W": W, "X": wx, "R": wr, "Out": wo,
                            "K": K, "ktiles": KTILES})     # pay JIT once
    for b in (wx, wr, wo):
        b.free()
    live, results = [], []
    submitted = {n: 0 for n in tenants}
    t0 = time.perf_counter()
    while len(results) < requests_per_tenant * len(tenants) or live:
        for name in tenants:
            t = front.tenants[name]
            while (submitted[name] < requests_per_tenant
                   and len(t.inflight) < t.max_inflight):
                x = rng.normal(size=K).astype(np.float32)
                r = rng.normal(size=M).astype(np.float32)
                xb = s.alloc(K).copy_from_host(x)
                rb = s.alloc(M).copy_from_host(r)
                ob = s.alloc(M)
                tk = front.submit(name, fn, GRID, BLOCK,
                                  {"W": W, "X": xb, "R": rb, "Out": ob,
                                   "K": K, "ktiles": KTILES})
                d2h = ob.copy_to_host_async(stream=t.stream)
                live.append((tk, d2h, (xb, rb, ob), (x, r)))
                submitted[name] += 1
        front.pump(32)
        still = []
        for tk, d2h, bufs, host in live:
            if tk.done() and d2h.done():
                results.append((tk, d2h.result(), host))
                for b in bufs:
                    b.free()
            else:
                still.append((tk, d2h, bufs, host))
        live = still
    front.drain()
    dt = time.perf_counter() - t0

    # spot-check a handful of results against the oracle
    for tk, out, (x, r) in results[::17]:
        want = oracle({"W": W.copy_to_host(), "X": x, "R": r,
                       "Out": np.zeros(M, np.float32),
                       "K": K, "ktiles": KTILES})["Out"]
        np.testing.assert_allclose(out, want, atol=1e-4, rtol=1e-4)
    agg = front.stats()
    pool = s.pool_stats()
    print(f"served {agg['completed']} decode matvecs from "
          f"{len(tenants)} tenants in {dt*1e3:.0f} ms "
          f"(p50 {agg.get('p50_ms', 0):.2f} / "
          f"p99 {agg.get('p99_ms', 0):.2f} ms, "
          f"{agg['rejected']} shed, "
          f"pool reuse {pool['reuse_rate']:.0%})")
    for t in agg["tenants"]:
        print(f"  {t['tenant']:<7} w={t['weight']:.0f} "
              f"completed={t['completed']} p99={t.get('p99_ms', 0):.2f}ms")
    print("results verified against the decode_gemv oracle")


def zoo_demo(new_tokens: int = 6) -> None:
    """Greedy decode with the model-zoo attention kernel, live-migrated
    mid-token across all three backends (no jax).

    The "model" is deliberately tiny — an embedding table, a circular
    KV window and an output projection in host numpy — because the star
    is the attention kernel: one hetIR ``attn_decode`` Program serves
    every token through the ServingFrontEnd, and on the migration token
    the in-flight launch is paused inside the online-softmax tile loop
    (m/l/acc in the regfile, the probability tile in shared memory),
    checkpointed, and resumed interp -> vectorized -> pallas.  Because
    the zoo oracle reproduces the kernel's exact float32 op order, the
    logits are asserted **bit-identical** every token, migrated or not.
    """
    import tempfile

    import repro.zoo as zoo
    from repro.core import HetSession, ServingFrontEnd, migrate

    H, D, T, NT = 4, zoo.ATTN_D, zoo.ATTN_T, 3
    CTX = NT * T                     # fixed circular KV window
    VOCAB = 16
    GRID, BLOCK = H, T

    prog, oracle = zoo.attn_decode()
    fabric = tempfile.mkdtemp(prefix="zoo-fabric-")
    sessions = {n: HetSession(n, shared=fabric)
                for n in ("interp", "vectorized", "pallas")}
    for s in sessions.values():
        s.load(prog)
    src = sessions["interp"]
    fn = src.load(prog).function()
    front = ServingFrontEnd(src, default_quota=4, slo_ms=5000.0)
    front.tenant("decoder", weight=1.0)

    rng = np.random.default_rng(42)
    emb = (rng.normal(size=(VOCAB, H * D)) * 0.3).astype(np.float32)
    w_out = (rng.normal(size=(H * D, VOCAB)) * 0.2).astype(np.float32)
    kcache = (rng.normal(size=(H, CTX, D)) * 0.3).astype(np.float32)
    vcache = (rng.normal(size=(H, CTX, D)) * 0.3).astype(np.float32)
    scale = float(np.float32(1.0 / np.sqrt(D)))

    token, migrations, tokens_out = 3, 0, []
    migrate_step = 1                 # this token's launch takes the tour
    for step in range(new_tokens):
        x = emb[token]
        kcache[:, step % CTX, :] = (x * 0.5).reshape(H, D)
        vcache[:, step % CTX, :] = np.tanh(x).reshape(H, D).astype(
            np.float32)
        host = {"Q": x.copy(), "K": kcache.reshape(-1).copy(),
                "V": vcache.reshape(-1).copy(),
                "O": np.zeros(H * D, np.float32),
                "ntiles": NT, "scale": scale}
        expect_o = oracle({k: (v.copy() if isinstance(v, np.ndarray)
                               else v) for k, v in host.items()})["O"]

        bufs = {k: src.alloc(v.size).copy_from_host(v)
                for k, v in host.items() if isinstance(v, np.ndarray)}
        tk = front.submit("decoder", fn, GRID, BLOCK,
                          {**bufs, "ntiles": NT, "scale": scale})
        if step == migrate_step:
            # pause inside the online-softmax tile loop, then hop twice
            rec = tk.record
            rec.advance(max_segments=3)
            rec = migrate(rec, src, sessions["vectorized"], "attn_decode")
            migrations += 1
            rec.advance(max_segments=2)
            rec = migrate(rec, sessions["vectorized"],
                          sessions["pallas"], "attn_decode")
            migrations += 1
            sessions["pallas"].run_to_completion(rec)
            got_o = rec.buffer("O").copy_to_host()
            mig = sessions["pallas"].stats["last_migration"]
            print(f"  token {step}: migrated mid-softmax "
                  f"interp->vectorized->pallas "
                  f"(payload {mig['payload_bytes']/1024:.1f} kB, "
                  f"fabric translations restored: "
                  f"{mig['cache_restored']})")
        else:
            while not tk.done():
                front.pump(16)
            got_o = bufs["O"].copy_to_host()
        for b in bufs.values():
            if step != migrate_step:     # migrated buffers moved sessions
                b.free()

        logits = got_o @ w_out
        want = expect_o @ w_out
        np.testing.assert_array_equal(
            logits, want,
            err_msg=f"token {step}: served logits diverge from oracle")
        token = int(np.argmax(logits))
        tokens_out.append(token)

    front.drain()
    assert migrations >= 2, "demo must migrate the decode at least twice"
    print(f"decoded {new_tokens} tokens via zoo attn_decode "
          f"({migrations} cross-backend mid-decode migrations); "
          f"logits bit-identical to the oracle every token")
    print("sampled ids:", tokens_out)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--het-tier", action="store_true",
                    help="serve decode matvecs through the hetGPU "
                         "multi-tenant serving tier instead of jax")
    ap.add_argument("--requests", type=int, default=24,
                    help="(--het-tier) requests per tenant")
    ap.add_argument("--zoo", action="store_true",
                    help="decode through the model-zoo attn_decode "
                         "kernel with mid-token cross-backend migration")
    ap.add_argument("--arch", default="llama3.2-3b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=16)
    args = ap.parse_args()

    if args.zoo:
        zoo_demo(min(args.new_tokens, 8))
        return
    if args.het_tier:
        het_tier(args.requests)
        return

    import jax
    import jax.numpy as jnp

    from repro import configs
    from repro.models import decode_step, prefill
    if args.arch not in configs.list_archs():
        ap.error(f"unknown --arch {args.arch}")

    cfg = configs.get_smoke_config(args.arch)
    rng = np.random.default_rng(0)
    from repro.models import init_params
    params = init_params(jax.random.key(0), cfg)

    B, S = args.batch, args.prompt_len
    batch = {"tokens": jnp.asarray(
        rng.integers(0, cfg.vocab_size, (B, S)), jnp.int32)}
    if cfg.encoder_decoder:
        batch["enc_embeds"] = jnp.asarray(
            rng.normal(size=(B, S, cfg.d_model)) * 0.02,
            jnp.dtype(cfg.compute_dtype))
    if cfg.frontend == "patch":
        batch["embeds"] = jnp.asarray(
            rng.normal(size=(B, cfg.frontend_tokens, cfg.d_model)) * 0.02,
            jnp.dtype(cfg.compute_dtype))
        batch["tokens"] = batch["tokens"][:, cfg.frontend_tokens:]

    cache_len = S + args.new_tokens
    t0 = time.perf_counter()
    logits, caches = jax.jit(
        lambda p, b: prefill(p, b, cfg, cache_len=cache_len))(params, batch)
    print(f"prefill {B}x{S}: {(time.perf_counter()-t0)*1e3:.0f} ms")

    step = jax.jit(lambda p, t, c, q: decode_step(p, t, c, q, cfg))
    toks = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
    out = [toks]
    t0 = time.perf_counter()
    for i in range(args.new_tokens - 1):
        logits, caches = step(params, toks, caches,
                              jnp.asarray(S + i, jnp.int32))
        toks = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
        out.append(toks)
    jax.block_until_ready(toks)
    dt = time.perf_counter() - t0
    n = (args.new_tokens - 1) * B
    print(f"decode: {n} tokens in {dt*1e3:.0f} ms "
          f"({n/dt:.1f} tok/s greedy, batch={B})")
    print("sampled ids:", np.asarray(jnp.concatenate(out, axis=1))[0][:12])


if __name__ == "__main__":
    configure_compile_cache()
    main()
