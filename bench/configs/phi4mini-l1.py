"""``phi4mini-l1``: phi4-mini decoder layer 0 at its published widths,
served by ``DecodeSession`` on one shared ``TMServer(backend="pallas")``.

A request is one sequence: its prompt (token ids drawn from the seed) runs
through ``DecodeSession.prefill``, then ``output_len - 1`` greedy steps
through ``DecodeSession.decode``, each token taken on the host.  The KV
cache rides the responses from step to step.  The traffic file gives the
prompt and output lengths; the cache holds ``prompt + output`` positions,
rounded up to a multiple of 128.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from bench.audit import audit
from bench.harness import ROOT, load_module
from bench.schedule import seed_key

_REF = ROOT / "bench" / "configs" / "phi4mini-l1.reference.py"
# widths of the small size the CPU tests run (phi4_mini smoke widths)
SMALL = {"hidden_size": 48, "intermediate_size": 96,
         "num_attention_heads": 3, "num_key_value_heads": 1, "head_dim": 16,
         "vocab_size": 512}


def make_weights(spec: dict, key):
    """All weights in one jitted call on the device, in bfloat16: every
    matrix and the embedding N(0, initializer_range^2), as the published
    config initializes them, and norm gains 1 + 0.1 N(0, 1)."""
    D, F, V = spec["hidden_size"], spec["intermediate_size"], \
        spec["vocab_size"]
    H, KV, hd = spec["num_attention_heads"], spec["num_key_value_heads"], \
        spec["head_dim"]
    bf = jnp.bfloat16
    std = float(spec["initializer_range"])

    def init(key):
        k = jax.random.split(key, 8)

        def mat(i, shape):
            return (jax.random.normal(k[i], shape, jnp.float32)
                    * std).astype(bf)

        def gain(i):
            return 1.0 + 0.1 * jax.random.normal(k[i], (D,), jnp.float32)

        return {
            "embed": {"e": mat(0, (V, D))},
            "final_norm": {"g": gain(1)},
            "blocks": {
                "attn": {"wqkv": mat(2, (1, D, (H + 2 * KV) * hd)),
                         "wo": mat(3, (1, H * hd, D))},
                "mlp": {"wi": mat(4, (1, D, 2 * F)),
                        "wo": mat(5, (1, F, D))},
                "ln1": {"g": gain(6)[None]}, "ln2": {"g": gain(7)[None]},
            },
        }

    return jax.jit(init)(key)


def step_flops(spec: dict, new: int, kv_len: int, logit_rows: int) -> float:
    """Model FLOPs of one served step: ``new`` tokens through the layer,
    attending to ``kv_len`` cached positions (causal within the new ones),
    and ``logit_rows`` rows of the tied unembedding.  Matmuls only, 2 per
    multiply-add; padding rows of a group add nothing."""
    D, F, V = spec["hidden_size"], spec["intermediate_size"], \
        spec["vocab_size"]
    H, KV, hd = spec["num_attention_heads"], spec["num_key_value_heads"], \
        spec["head_dim"]
    per_token = D * (H + 2 * KV) * hd + H * hd * D + D * 2 * F + F * D
    # score and value products over the keys each new token sees
    keys = sum(kv_len - new + i + 1 for i in range(new))
    attn = 2 * H * hd * keys
    return 2.0 * (new * per_token + logit_rows * D * V + attn)


def step_bytes(spec: dict, height: int, kv_len: int) -> float:
    """Least HBM bytes of one decode step of a group of ``height``: every
    weight once (bf16) and each sequence's KV cache read once."""
    D, F, V = spec["hidden_size"], spec["intermediate_size"], \
        spec["vocab_size"]
    H, KV, hd = spec["num_attention_heads"], spec["num_key_value_heads"], \
        spec["head_dim"]
    weights = (D * (H + 2 * KV) * hd + H * hd * D + D * 2 * F + F * D
               + V * D) * 2 + 3 * D * 4
    return weights + height * 2 * kv_len * KV * hd * 2


class Deployment:
    """The served path: one ``DecodeSession`` on one ``TMServer``."""

    def __init__(self, spec: dict, traffic: dict, seed: int, *,
                 small: bool = False, trace: bool = False):
        from repro.models.transformer import ModelConfig
        from repro.serving import DecodeSession, ServerConfig, TMServer
        self.spec = dict(spec, **SMALL) if small else dict(spec)
        s = self.spec
        self.seed = seed
        self.prompt_len = int(traffic["prompt_len"])
        self.output_len = int(traffic["output_len"])
        self.check_every = int(traffic["check_every"])
        self.offset = int(np.random.default_rng([seed, 3]).integers(
            self.check_every))
        self.max_len = -(-(self.prompt_len + self.output_len - 1) // 128) \
            * 128
        self.cfg = ModelConfig(
            name="phi4mini-l1", family="dense", n_layers=1,
            d_model=s["hidden_size"], n_heads=s["num_attention_heads"],
            n_kv_heads=s["num_key_value_heads"], head_dim=s["head_dim"],
            d_ff=s["intermediate_size"], vocab=s["vocab_size"],
            rope_theta=float(s["rope_theta"]),
            max_seq=s["max_position_embeddings"], dtype=jnp.bfloat16)
        self.weights = make_weights(s, seed_key(seed))
        srv = s["server"]
        self.heights = (1, 2) if small else tuple(srv["heights"])
        self.server = TMServer(ServerConfig(
            backend=srv["backend"], max_batch=srv["max_batch"],
            batch_timeout_s=srv["batch_timeout_s"],
            cache_capacity=srv["cache_capacity"], exact=s["program"]["exact"],
            trace=True if trace else None)).start()
        self.session = DecodeSession(self.cfg, self.weights,
                                     max_len=self.max_len,
                                     server=self.server)
        self._stopped = False

    # -- requests ----------------------------------------------------------

    def prompt(self, i: int) -> np.ndarray:
        rng = np.random.default_rng([self.seed, i])
        return rng.integers(0, self.spec["vocab_size"],
                            (1, self.prompt_len), dtype=np.int32)

    def _pick(self, logits, keep: bool):
        """The greedy token of the last logit row, taken on the host, and
        the row itself when the check keeps it."""
        row = logits[0, -1]
        return int(jnp.argmax(row)), (np.asarray(row) if keep else None)

    def serve(self, i: int, rec) -> dict:
        """One sequence: prefill, then greedy decode steps, each token
        taken on the host.  Returns the served token ids, and for the
        seed's sample of sequences the logit rows they were taken from."""
        keep = i % self.check_every == self.offset
        toks, rows = [], []
        cache = None
        for k in range(self.output_len):
            with jax.profiler.TraceAnnotation("bench/step"):
                if k == 0:
                    logits, cache = self.session.prefill(self.prompt(i))
                else:
                    logits, cache = self.session.decode(
                        np.array([[toks[-1]]], np.int32), cache,
                        self.prompt_len + k - 1)
            tok, row = self._pick(logits, keep)
            toks.append(tok)
            rows.append(row)
            rec.events.append(time.monotonic())
        return {"tokens": toks, "logits": np.stack(rows) if keep else None}

    def _drive(self, h: int) -> None:
        """``h`` sequences in lockstep, each stage submitted at once (the
        calls ``DecodeSession.prefill`` and ``decode`` make)."""
        sess, srv = self.session, self.server
        ck, cv = sess.init_cache(1)
        futs = [srv.submit(sess.step_fn(0), self.prompt(2**40 + j), ck, cv,
                           fn_key=sess._fn_key(0, self.prompt_len))
                for j in range(h)]
        for k in range(1, self.output_len + 1):
            outs = [f.result() for f in futs]
            picks = [self._pick(o[0], True) for o in outs]
            if k == self.output_len:
                return
            p = self.prompt_len + k - 1
            futs = [srv.submit(sess.step_fn(p), np.array([[t]], np.int32),
                               o[1], o[2], fn_key=sess._fn_key(p, 1))
                    for (t, _), o in zip(picks, outs)]

    def event_flops(self, i: int, k: int) -> float:
        if k == 0:
            return step_flops(self.spec, self.prompt_len, self.prompt_len,
                              self.prompt_len)
        return step_flops(self.spec, 1, self.prompt_len + k, 1)

    # -- set-up ------------------------------------------------------------

    def classes(self):
        """(label, fn, args, fn_key) of every served shape class."""
        sess = self.session
        ck, cv = sess.init_cache(1)
        out = [(f"prefill s{self.prompt_len}", sess.step_fn(0),
                (np.zeros((1, self.prompt_len), np.int32), ck, cv),
                sess._fn_key(0, self.prompt_len))]
        for p in range(self.prompt_len,
                       self.prompt_len + self.output_len - 1):
            out.append((f"decode p{p}", sess.step_fn(p),
                        (np.zeros((1, 1), np.int32), ck, cv),
                        sess._fn_key(p, 1)))
        return out

    def warm(self) -> None:
        from bench.warm import warm_classes
        warm_classes(self.server, self.classes(), self.heights, self._drive)
        self.server.flush()

    def reset_series(self) -> None:
        self.server.stats.reset_series()

    def cache_misses(self) -> int:
        return self.server.cache.snapshot()["misses"]

    def queue_delays(self) -> list:
        return list(self.server.stats.queue_delay_s)

    def tracer(self):
        return self.server.tracer

    def audit(self) -> dict:
        return audit(self.server)

    def stop(self) -> None:
        if not self._stopped:
            self._stopped = True
            self.session.close()
            self.server.stop()
            self.session = None

    # -- correctness -------------------------------------------------------

    def check(self, done: list, seed: int) -> dict:
        """Over the seed's sample of finished sequences (every request whose
        index is the seed's residue modulo ``check_every``): the largest,
        over the prefill's last position and every decode step, of the RMS
        difference between the served logits and the float32 reference's,
        in standard deviations of the reference row."""
        limit = self.spec["limits"]["logit_rms_err"]
        kept = [r for r in done if r.kept["logits"] is not None]
        if not kept:
            return {"logit_rms_err": {"value": None, "limit": limit}}
        return {"logit_rms_err": {
            "value": float(self.reference_errors(kept).max()),
            "limit": limit}}

    def reference_errors(self, recs, *, quantized: bool = False):
        """Per logit row, RMS(served - reference) / std(reference).  With
        ``quantized`` the control (the float8 reference) stands in the
        program's place, at the same prompts and tokens."""
        ref = load_module(_REF)
        toks = np.array([r.kept["tokens"] for r in recs], np.int32)
        fed = np.concatenate([np.concatenate([self.prompt(r.index)
                                              for r in recs]),
                              toks[:, :-1]], axis=1)
        lo, hi = self.prompt_len - 1, fed.shape[1]
        want = ref.logits(self.weights, self.spec, fed, lo, hi)
        if quantized:
            got = ref.logits(self.weights, self.spec, fed, lo, hi,
                             quantized=True)
        else:
            got = np.stack([r.kept["logits"] for r in recs])
        return ref.rms_err_sigma(got.astype(np.float32), want)

    def reference_greedy(self, n: int) -> list:
        """The reference's own greedy continuations of ``n`` prompts, as
        finished requests — the tokens the control is read at on a seed
        the program did not serve."""
        import types
        ref = load_module(_REF)
        T = self.prompt_len + self.output_len - 1
        lo = self.prompt_len - 1
        fed = np.zeros((n, T), np.int32)
        fed[:, :self.prompt_len] = np.concatenate(
            [self.prompt(i) for i in range(n)])
        toks = np.zeros((n, self.output_len), np.int32)
        for k in range(self.output_len):
            rows = ref.logits(self.weights, self.spec, fed, lo, T)
            toks[:, k] = rows[:, k].argmax(-1)
            if lo + k + 1 < T:
                fed[:, lo + k + 1] = toks[:, k]
        return [types.SimpleNamespace(index=i, kept={"tokens": list(toks[i])})
                for i in range(n)]
