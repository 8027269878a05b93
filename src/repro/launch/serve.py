"""Serving drivers.

LM loop — batched prefill + decode with KV caches/SSM states:

  PYTHONPATH=src python -m repro.launch.serve --arch rwkv6-3b --smoke \
      --batch 4 --prompt-len 32 --gen 32

CNN demo blocks through the TMU serving runtime (``repro.serving``):

  PYTHONPATH=src python -m repro.launch.serve --cnn --requests 24 \
      --max-batch 4 --backend fused
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs.registry import get_config, get_smoke, list_archs
from repro.models.transformer import init_caches, init_lm, init_states
from repro.obs.tracer import as_tracer
from repro.platform import enable_compile_cache
from repro.runtime.step import make_decode_step, make_prefill_step


def serve(cfg, *, batch=4, prompt_len=32, gen=32, seed=0, log=print,
          tracer=None):
    tracer = as_tracer(tracer)
    params, _ = init_lm(cfg, jax.random.PRNGKey(seed))
    max_len = prompt_len + gen
    caches = init_caches(cfg, batch, max_len,
                         dtype=jnp.float32 if cfg.dtype == jnp.float32
                         else jnp.bfloat16)
    states = init_states(cfg, batch)
    prompts = jax.random.randint(jax.random.PRNGKey(seed + 1),
                                 (batch, prompt_len), 0, cfg.vocab)

    prefill = jax.jit(make_prefill_step(cfg), donate_argnums=(2, 3))
    decode = jax.jit(make_decode_step(cfg), donate_argnums=(2, 3),
                     static_argnames=())

    t0 = time.monotonic()
    with tracer.span(f"prefill@{batch}x{prompt_len}", track="lm",
                     batch=batch, prompt_len=prompt_len):
        lg, caches, states = prefill(params, prompts, caches, states)
        tok = jnp.argmax(lg[:, -1], axis=-1)[:, None].astype(jnp.int32)
        jax.block_until_ready(tok)
    t_prefill = time.monotonic() - t0

    if gen <= 0:
        # prefill-only run: no decode loop, no generated tokens — report
        # prefill throughput instead of dividing by a decode time that
        # never ran (which used to yield a negative tokens/s)
        tps = batch * prompt_len / max(t_prefill, 1e-9)
        log(f"prefill {batch}x{prompt_len}: {t_prefill*1e3:.1f} ms "
            f"({tps:.1f} prompt tok/s, prefill-only)")
        return jnp.zeros((batch, 0), dtype=jnp.int32), {
            "prefill_s": t_prefill, "decode_s": 0.0,
            "tokens_per_s": tps, "prefill_only": True}

    out = [tok]
    t0 = time.monotonic()
    traced = tracer.enabled
    for t in range(prompt_len, prompt_len + gen - 1):
        if traced:
            with tracer.span(f"decode/step@p{t}", track="lm"):
                tok, lg, caches, states = decode(params, tok, caches,
                                                 states, t)
                jax.block_until_ready(tok)
        else:
            tok, lg, caches, states = decode(params, tok, caches, states, t)
        out.append(tok)
    jax.block_until_ready(tok)
    t_decode = time.monotonic() - t0
    toks = jnp.concatenate(out, axis=1)
    tps = batch * (gen - 1) / max(t_decode, 1e-9)
    log(f"prefill {batch}x{prompt_len}: {t_prefill*1e3:.1f} ms; "
        f"decode {gen-1} steps: {t_decode*1e3:.1f} ms ({tps:.1f} tok/s)")
    return toks, {"prefill_s": t_prefill, "decode_s": t_decode,
                  "tokens_per_s": tps}


def serve_cnn(*, n_requests=24, max_batch=4, backend="fused", seed=0,
              log=print, tracer=None):
    """Drive the paper's CNN demo blocks through :class:`TMServer`.

    Mixed traffic over the tm_compile demo fragments (``superres_tail`` /
    ``yolo_neck`` / ``detect_tail``, plus whole ``espcn`` — conv compute
    feeding a TM tail) in two shape classes each — the shape-bucketed
    batcher coalesces per class, the compile cache de-duplicates, and the
    two-engine pipeline overlaps TM phases of one micro-batch with opaque
    conv compute of the next.  Every response is checked bit-exact against
    the direct call."""
    import numpy as np

    from repro.models import cnn
    from repro.serving import ServerConfig, TMServer

    rng = np.random.RandomState(seed)

    def arr(*shape):
        return jnp.asarray(rng.rand(*shape).astype(np.float32))

    def detect(pred):
        return cnn.detect_tail(pred, 0.5, 16)

    espcn_params = cnn.init_espcn(jax.random.PRNGKey(seed), s=2)

    def espcn(img):
        return cnn.espcn(espcn_params, img)

    workload = []
    for i in range(n_requests):
        kind = i % 4
        odd = (i // 4) % 2  # alternate shape classes inside each fn bucket
        if kind == 0:
            x = arr(1, 6 + 2 * odd, 10, 8)
            skip = arr(1, (6 + 2 * odd) * 2, 20, 2)
            workload.append(("superres", cnn.superres_tail, (x, skip)))
        elif kind == 1:
            u = arr(1, 4, 6 + 2 * odd, 6)
            skip = arr(1, 8, (6 + 2 * odd) * 2, 3)
            workload.append(("yolo_neck", cnn.yolo_neck, (u, skip)))
        elif kind == 2:
            workload.append(("detect_tail", detect, (arr(2, 33 + odd, 7),)))
        else:
            workload.append(("espcn", espcn, (arr(1, 8 + 2 * odd, 10, 3),)))

    t0 = time.monotonic()
    with TMServer(ServerConfig(max_batch=max_batch, backend=backend,
                               batch_timeout_s=0.01, trace=tracer)) as srv:
        futs = [(fn, args, srv.submit(fn, *args, fn_key=key))
                for key, fn, args in workload]
        for fn, args, fut in futs:
            got = fut.result()
            want = fn(*args)
            assert jnp.array_equal(jnp.asarray(got), jnp.asarray(want)), \
                "served result diverged from direct call"
        stats = srv.snapshot_stats()
    wall = time.monotonic() - t0
    stats["wall_s"] = wall
    stats["requests_per_s"] = n_requests / max(wall, 1e-9)
    log(f"served {n_requests} CNN-block requests in {wall:.2f}s "
        f"({stats['requests_per_s']:.1f} req/s); "
        f"cache {stats['cache']['hits']}/{stats['cache']['hits'] + stats['cache']['misses']} hit, "
        f"mean batch {stats['mean_batch_size']:.2f}, "
        f"overlap {stats['overlap_ratio']:.1%} measured / "
        f"{stats['predicted_overlap']:.1%} predicted")
    return stats


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list_archs())
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--cnn", action="store_true",
                    help="serve the CNN demo blocks through TMServer")
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--backend", default="fused",
                    choices=("reference", "fused", "pallas"))
    ap.add_argument("--trace", metavar="OUT.json", default=None,
                    help="record a span timeline and export Chrome-trace "
                         "JSON (open at https://ui.perfetto.dev)")
    args = ap.parse_args(argv)
    enable_compile_cache()
    tracer = as_tracer(bool(args.trace))
    if args.cnn:
        serve_cnn(n_requests=args.requests, max_batch=args.max_batch,
                  backend=args.backend, tracer=tracer)
    else:
        if args.arch is None:
            ap.error("--arch is required unless --cnn is given")
        cfg = get_smoke(args.arch) if args.smoke else get_config(args.arch)
        toks, stats = serve(cfg, batch=args.batch,
                            prompt_len=args.prompt_len, gen=args.gen,
                            tracer=tracer)
        print("generated token ids (first row):", toks[0][:16].tolist())
    if args.trace:
        trace = tracer.export_chrome_trace(args.trace)
        print(f"trace: {len(trace['traceEvents'])} events -> {args.trace}")


if __name__ == "__main__":
    main()
