"""Chip smoke: serve phi4-mini layer decode and YOLOv3-Tiny through TMServer.

    python chip_smoke.py

Runs on one TPU chip, in one process, and refuses anything else.  Two phases,
both through ``TMServer`` with ``backend="pallas"`` (Pallas kernels compiled
by Mosaic, since interpret mode follows the platform):

* **decode** — phi4-mini at its published widths, cut to the one decoder
  layer ``DecodeSession`` serves; batch 4, a 128-token prompt, ``max_len``
  256, prefill + 4 decode steps, checked against ``reference_generate``
  (token ids equal, logits within a stated bf16 tolerance: each TPU phase
  runs as one jitted XLA computation with its dead inputs donated, so XLA
  may fuse across equations and round differently from eager dispatch);
* **cnn** — ``yolov3_tiny`` at the paper's 448x448x3 input, 8 requests at
  ``max_batch`` 4, plus a ``detect_tail_raw`` request class on each served
  28x28x255 head grid; the TM-only detect outputs must be bit-exact against
  ``jax.jit`` of the same function, the conv outputs within a stated f32
  tolerance.

Each phase prints its lowering paths, engine declines with their reasons,
launches, compile-cache accounting, per-request smoke timings and device
memory in use.  Any degraded lowering, quarantined kernel, backend-ladder
fallback, group fault, isolation retry, phase whose jit was declined,
phase run on a TPU without buffer donation, or disagreeing result fails the
run, with no ok line.  The last line of a
passing run is ``{"ok": true, "device": {...}}``.

The phase functions take their configuration and sizes as arguments, so a
CPU test can run them at smoke size.
"""

from __future__ import annotations

import collections
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

# decode logits are bf16, and the served TPU phases are fused XLA programs
# while the reference dispatches op by op: served and reference logits must
# agree within a few bf16 ulps of the largest reference logit
LOGIT_TOL = 2.0 ** -6
# convolution outputs are f32 computed at the device's default matmul
# precision, by differently fused programs on the two sides
CONV_TOL = 2e-2


class SmokeFailure(RuntimeError):
    """A check of the smoke run failed."""


def _device_line() -> dict:
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def _bytes_in_use():
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("bytes_in_use")


def audit(server) -> dict:
    """Lowering paths, declines, launches, cache and fault counters of a
    server's entries — raising :class:`SmokeFailure` on any degradation."""
    from repro.compiler.api import _JIT_DECLINED, TPUPhaseReport
    problems = []
    paths: collections.Counter = collections.Counter()
    declines: collections.Counter = collections.Counter()
    launches = collections.Counter()
    donated = 0
    entries = server.cache.entries()
    for e in entries:
        name = str(e.key.fn_key)
        if e.quarantine:
            problems.append(f"{name}: quarantined {sorted(e.quarantine)}")
        if e.degraded_phases:
            problems.append(f"{name}: phases fell down the backend ladder "
                            f"{e.degraded_phases}")
        # why a chain or crossing the admission sweep modeled did not realize
        for sweep in ("fuse_chains", "cross_engine"):
            for why in e.selection.get(sweep, {}).get("declines", ()):
                declines[f"admission {sweep} probe: {why}"] += 1
        for ph in e.compiled.partition_report.phases:
            if ph.kind == "tpu" and ph.jit_fn is _JIT_DECLINED:
                problems.append(f"{name}: TPU phase {ph.index} jit declined")
            donated += len(getattr(ph, "donated", None) or ())
        for rep in e.lowerings.values():
            if isinstance(rep, TPUPhaseReport):
                paths["xla"] += rep.n_eqns
                launches["xla computations"] += rep.xla_computations
                continue
            for why in rep.declines:
                declines[why] += 1
            for r in rep.records:
                if r.degraded:
                    problems.append(f"{name}: degraded {r.path} ({r.reason})")
                kind = r.path.split(".")[0]
                paths[r.path if kind == "pallas" else
                      ("engine" if kind == "reference" else "xla")] \
                    += r.instrs
                launches["pallas" if kind == "pallas" else
                         ("engine" if kind == "reference" else
                          "xla computations")] += r.launches
                if r.reason:
                    declines[r.reason] += 1
    if jax.default_backend() == "tpu" and not donated:
        problems.append("no TPU phase donated a buffer")
    snap = server.snapshot_stats()
    for k in ("degraded_phases", "group_faults", "isolation_retries"):
        if snap[k]:
            problems.append(f"server {k} = {snap[k]}")
    if problems:
        raise SmokeFailure("; ".join(problems))
    return {"entries": len(entries), "paths": dict(paths),
            "declines": dict(declines), "launches": dict(launches),
            "donated_buffers": donated,
            "cache_hits": snap["cache"]["hits"],
            "cache_misses": snap["cache"]["misses"],
            "compile_s": sum(e.compile_s for e in entries)}


def _report(name: str, summary: dict, log) -> None:
    log(f"[{name}] lowering paths (instructions): {summary['paths']}")
    for why, n in sorted(summary["declines"].items()):
        log(f"[{name}] decline x{n}: {why}")
    log(f"[{name}] launches: {summary['launches']}; donated buffers: "
        f"{summary['donated_buffers']}")
    log(f"[{name}] compile cache: {summary['cache_hits']} hits, "
        f"{summary['cache_misses']} misses, "
        f"{summary['compile_s']:.3f} s compiling")
    log(f"[{name}] smoke timings, not a benchmark (s per request): "
        f"{[round(t, 4) for t in summary['request_s']]}")
    log(f"[{name}] device bytes in use after the phase: "
        f"{summary['bytes_in_use']}")


def decode_phase(cfg, *, batch: int = 4, prompt_len: int = 128,
                 max_len: int = 256, steps: int = 5, seed: int = 0,
                 log=print) -> dict:
    """Serve prefill + ``steps - 1`` decode steps of one decoder layer and
    check them against ``reference_generate``."""
    from repro.models.transformer import init_lm
    from repro.serving import DecodeSession, ServerConfig
    layer = dataclasses.replace(cfg, n_layers=1)
    log(f"[decode] {cfg.name}: n_layers {cfg.n_layers} -> 1 (the layer "
        f"DecodeSession serves); d_model {layer.d_model}, heads "
        f"{layer.n_heads}/{layer.n_kv_heads} kv, head_dim {layer.hd}, "
        f"d_ff {layer.d_ff}, vocab {layer.vocab}, {jnp.dtype(layer.dtype)}")
    params, _ = init_lm(layer, jax.random.PRNGKey(seed))
    prompts = jax.random.randint(jax.random.PRNGKey(seed + 1),
                                 (batch, prompt_len), 0, layer.vocab,
                                 dtype=jnp.int32)
    config = ServerConfig(backend="pallas", max_batch=4, batch_timeout_s=0.0,
                          cache_capacity=max_len + 8)
    with DecodeSession(layer, params, max_len=max_len,
                       config=config) as sess:
        toks, logits = sess.generate(prompts, steps)
        jax.block_until_ready(logits)
        summary = audit(sess.server)
        summary["request_s"] = (list(sess.stats.prefill_latency_s)
                                + list(sess.stats.step_latency_s))
        ref_toks, ref_logits = sess.reference_generate(prompts, steps)
    if not np.array_equal(np.asarray(toks), np.asarray(ref_toks)):
        raise SmokeFailure(f"decode tokens differ: served "
                           f"{np.asarray(toks).tolist()} vs reference "
                           f"{np.asarray(ref_toks).tolist()}")
    err, bound = 0.0, 0.0
    for got, want in zip(logits, ref_logits):
        g = np.asarray(got, np.float32)
        w = np.asarray(want, np.float32)
        if g.shape != w.shape or not np.isfinite(g).all():
            raise SmokeFailure(f"decode logits malformed: {g.shape}")
        err = max(err, float(np.abs(g - w).max()))
        bound = max(bound, LOGIT_TOL * float(np.abs(w).max()))
    log(f"[decode] tokens equal the reference ({np.asarray(toks).shape}); "
        f"logits max abs err {err} (tolerance {bound} = {LOGIT_TOL} x "
        f"max |reference|)")
    if err > bound:
        raise SmokeFailure(f"decode logits err {err} > {bound}")
    summary["bytes_in_use"] = _bytes_in_use()
    summary["logit_max_abs_err"] = err
    _report("decode", summary, log)
    return summary


def cnn_phase(*, image: int = 448, n_requests: int = 8, max_batch: int = 4,
              seed: int = 0, log=print) -> dict:
    """Serve YOLOv3-Tiny requests, then ``detect_tail_raw`` on each served
    head grid, and check both against ``jax.jit`` of the same functions."""
    from repro.models import cnn
    from repro.serving import ServerConfig, TMServer
    params = cnn.init_yolov3_tiny(jax.random.PRNGKey(seed))

    def yolo(img):
        return cnn.yolov3_tiny(params, img)

    imgs = [jax.random.uniform(jax.random.PRNGKey(seed + 1 + i),
                               (1, image, image, 3), jnp.float32)
            for i in range(n_requests)]
    log(f"[cnn] yolov3_tiny at {image}x{image}x3, {n_requests} requests, "
        f"max_batch {max_batch}; detect_tail_raw on each head grid")
    done: dict = {}

    def timed(srv, fn, x, key):
        t0 = time.monotonic()
        fut = srv.submit(fn, x, fn_key=key)
        fut.add_done_callback(
            lambda f, t0=t0: done.setdefault(id(f), time.monotonic() - t0))
        return fut

    config = ServerConfig(backend="pallas", max_batch=max_batch,
                          batch_timeout_s=0.05)
    with TMServer(config) as srv:
        futs = [timed(srv, yolo, x, "yolov3_tiny") for x in imgs]
        preds = [f.result() for f in futs]
        grids = [p2 for _, p2 in preds]
        dfuts = [timed(srv, cnn.detect_tail_raw, g, "detect_tail_raw")
                 for g in grids]
        dets = [f.result() for f in dfuts]
        summary = audit(srv)
    summary["request_s"] = [done[id(f)] for f in futs + dfuts]

    ref_yolo, ref_det = jax.jit(yolo), jax.jit(cnn.detect_tail_raw)
    err, bound = 0.0, 0.0
    for x, got in zip(imgs, preds):
        for g, w in zip(got, ref_yolo(x)):
            g, w = np.asarray(g), np.asarray(w)
            if g.shape != w.shape or not np.isfinite(g).all():
                raise SmokeFailure(f"yolo output malformed: {g.shape}")
            err = max(err, float(np.abs(g - w).max()))
            bound = max(bound, CONV_TOL * float(np.abs(w).max()))
    if err > bound:
        raise SmokeFailure(f"yolo outputs err {err} > {bound}")
    for g, got in zip(grids, dets):
        if not np.array_equal(np.asarray(got), np.asarray(ref_det(g))):
            raise SmokeFailure("detect_tail_raw is not bit-exact vs jax.jit")
    log(f"[cnn] detect_tail_raw bit-exact vs jax.jit ({len(dets)} "
        f"requests); yolo max abs err {err} (tolerance {bound} = "
        f"{CONV_TOL} x max |reference|)")
    summary["bytes_in_use"] = _bytes_in_use()
    summary["conv_max_abs_err"] = err
    _report("cnn", summary, log)
    return summary


def main() -> int:
    device = _device_line()
    if device["platform"] != "tpu":
        print(f"chip_smoke: needs a TPU, found {device['platform']} "
              f"({device['kind']}); nothing was run", file=sys.stderr)
        return 2
    from repro.configs.phi4_mini_3p8b import config
    from repro.platform import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}")
    print(f"device: {device}")
    try:
        decode_phase(config())
        cnn_phase()
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
