"""``yolov3tiny-448``: darknet's YOLOv3-Tiny at the paper's 448x448x3
input, then ``detect_tail_raw`` on each of its two head grids, all served
by one ``TMServer(backend="pallas")``.  The network is built here from the
program's conv and TM ops (:func:`network`), layer for layer as the cfg.

A request is one image, sent from the host: the network runs as one served
call, and its two grids go back to the server as two detect-tail calls.
The request is answered when both tails' records are on the host.  The
convolutions run at ``highest`` matmul precision, as the configuration
states float32.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from bench.audit import audit
from bench.harness import ROOT, load_module
from bench.schedule import seed_key

_REF = ROOT / "bench" / "configs" / "yolov3tiny-448.reference.py"
SMALL = {"input_size": [64, 64, 3], "head_grids": [[2, 2], [4, 4]]}


def _shapes(spec: dict) -> dict:
    """(kh, kw, in, out) of every convolution, with the input channels the
    model really has (3 for the first conv, before the Rearrange's pad)."""
    c = [spec["input_size"][2]] + spec["backbone_channels"]
    no = spec["anchors_per_grid"] * (5 + spec["num_classes"])
    r, u = spec["head1_reduce_channels"], spec["up_reduce_channels"]
    routed = spec["backbone_channels"][spec["route_layer"]]
    return {"backbone": [(3, 3, c[i], c[i + 1]) for i in range(len(c) - 1)],
            "conv7": (3, 3, c[-1], spec["conv7_channels"]),
            "head1_reduce": (1, 1, spec["conv7_channels"], r),
            "head1": (3, 3, r, spec["head1_channels"]),
            "head1_out": (1, 1, spec["head1_channels"], no),
            "up_reduce": (1, 1, r, u),
            "head2": (3, 3, u + routed, spec["head2_channels"]),
            "head2_out": (1, 1, spec["head2_channels"], no)}


def make_weights(spec: dict, key):
    """Every conv's kernel and bias in one jitted call on the device,
    float32: kernels N(0, 1/fan_in), biases N(0, 0.1^2).  The first
    kernel's rows for the Rearrange's zero channels are zero."""
    shapes = _shapes(spec)
    flat, tree = jax.tree_util.tree_flatten(
        shapes, is_leaf=lambda s: isinstance(s, tuple))
    pad = spec["rearrange_channels"] - spec["input_size"][2]

    def init(key):
        ks = jax.random.split(key, 2 * len(flat))
        out = []
        for i, s in enumerate(flat):
            w = jax.random.normal(ks[2 * i], s, jnp.float32) \
                * (s[0] * s[1] * s[2]) ** -0.5
            if i == 0:
                w = jnp.pad(w, ((0, 0), (0, 0), (0, pad), (0, 0)))
            b = 0.1 * jax.random.normal(ks[2 * i + 1], (s[3],), jnp.float32)
            out.append({"w": w, "b": b})
        return jax.tree_util.tree_unflatten(tree, out)

    return jax.jit(init)(key)


def network(w, img, spec: dict):
    """The served network, from the program's conv and TM ops: the
    Rearrange, the backbone, head 1, and head 2's Upsample + Route."""
    from repro.core import tm_ops
    from repro.models import cnn
    slope = spec["leaky_slope"]

    def conv(x, p, act=True):
        y = cnn.conv2d(x, p["w"], p["b"])
        return jax.nn.leaky_relu(y, slope) if act else y

    x = tm_ops.rearrange(img, 1, spec["rearrange_channels"])
    for i, (p, s) in enumerate(zip(w["backbone"], spec["pool_strides"])):
        x = conv(x, p)
        if i == spec["route_layer"]:
            routed = x
        x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 2, 2, 1),
                                  (1, s, s, 1), "SAME")
    x = conv(x, w["conv7"])
    r = conv(x, w["head1_reduce"])
    pred1 = conv(conv(r, w["head1"]), w["head1_out"], act=False)
    u = tm_ops.upsample(conv(r, w["up_reduce"]), 2)
    x = conv(tm_ops.route([u, routed]), w["head2"])
    pred2 = conv(x, w["head2_out"], act=False)
    return pred1, pred2


def image_flops(spec: dict) -> float:
    """Model FLOPs of one image: every convolution, 2 per multiply-add, at
    its output size, over the real input channels (the detect tails
    compute nothing)."""
    H, W, _ = spec["input_size"]
    shapes = _shapes(spec)
    total, h, w = 0, H, W
    for (kh, kw, ci, co), s in zip(shapes["backbone"],
                                   spec["pool_strides"]):
        total += h * w * kh * kw * ci * co
        h, w = h // s, w // s
    for name in ("conv7", "head1_reduce", "head1", "head1_out",
                 "up_reduce"):
        kh, kw, ci, co = shapes[name]
        total += h * w * kh * kw * ci * co
    for name in ("head2", "head2_out"):
        kh, kw, ci, co = shapes[name]
        total += (2 * h) * (2 * w) * kh * kw * ci * co
    return 2.0 * total


def image_bytes(spec: dict, height: int) -> float:
    """Least HBM bytes of one group of ``height``: weights and biases once,
    each image read and both grids written once (float32)."""
    weights = sum(int(np.prod(s)) + s[3] for s in jax.tree_util.tree_leaves(
        _shapes(spec), is_leaf=lambda s: isinstance(s, tuple))) * 4
    H, W, C = spec["input_size"]
    no = spec["anchors_per_grid"] * (5 + spec["num_classes"])
    grids = sum(a * b for a, b in spec["head_grids"]) * no
    return weights + height * (H * W * C + grids) * 4


class Deployment:
    """The served path: the network and both detect tails on one server."""

    def __init__(self, spec: dict, traffic: dict, seed: int, *,
                 small: bool = False, trace: bool = False):
        from repro.models import cnn
        from repro.serving import ServerConfig, TMServer
        self.spec = dict(spec, **SMALL) if small else dict(spec)
        s = self.spec
        self.seed = seed
        self.check_every = int(traffic["check_every"])
        self.offset = int(np.random.default_rng([seed, 3]).integers(
            self.check_every))
        H, W, C = s["input_size"]
        rng = np.random.default_rng([seed, 2])
        self.images = rng.random((int(traffic["images"]), 1, H, W, C),
                                 dtype=np.float32)
        self.order = rng.permutation(len(self.images))
        self.weights = make_weights(s, seed_key(seed))
        weights, precision = self.weights, s["matmul_precision"]
        det = s["detect"]

        def yolo(img):
            with jax.default_matmul_precision(precision):
                return network(weights, img, s)

        def tail(grid):
            return cnn.detect_tail_raw(grid, det["conf_threshold"],
                                       det["capacity"])

        self.yolo, self.tail = yolo, tail
        srv = s["server"]
        self.heights = (1, 2) if small else tuple(srv["heights"])
        self.server = TMServer(ServerConfig(
            backend=srv["backend"], max_batch=srv["max_batch"],
            batch_timeout_s=srv["batch_timeout_s"],
            cache_capacity=srv["cache_capacity"],
            trace=True if trace else None)).start()
        self._stopped = False

    # -- requests ----------------------------------------------------------

    def image_index(self, i: int) -> int:
        return int(self.order[i % len(self.order)])

    def serve(self, i: int, rec):
        """One image: the network, then both detect tails.  Returns what
        the check needs, for the seed's sample of requests."""
        img = self.images[self.image_index(i)]
        srv = self.server
        with jax.profiler.TraceAnnotation("bench/submit"):
            fut = srv.submit(self.yolo, img, fn_key="yolov3_tiny")
        with jax.profiler.TraceAnnotation("bench/wait"):
            g1, g2 = fut.result()
        with jax.profiler.TraceAnnotation("bench/submit"):
            f1 = srv.submit(self.tail, g1, fn_key="detect_tail_raw")
            f2 = srv.submit(self.tail, g2, fn_key="detect_tail_raw")
        with jax.profiler.TraceAnnotation("bench/wait"):
            t1, t2 = np.asarray(f1.result()), np.asarray(f2.result())
        rec.events.append(time.monotonic())
        if i % self.check_every == self.offset:
            return (np.asarray(g1), np.asarray(g2), t1, t2)
        return None

    def _drive(self, h: int) -> None:
        """``h`` images at once through the network, then their ``2 h``
        detect tails at once."""
        srv = self.server
        futs = [srv.submit(self.yolo, self.images[j % len(self.images)],
                           fn_key="yolov3_tiny") for j in range(h)]
        grids = [f.result() for f in futs]
        tails = [srv.submit(self.tail, g[n], fn_key="detect_tail_raw")
                 for n in (0, 1) for g in grids]
        for f in tails:
            np.asarray(f.result())

    def event_flops(self, i: int, k: int) -> float:
        return image_flops(self.spec)

    # -- set-up ------------------------------------------------------------

    def classes(self):
        """(label, fn, args, fn_key) of every served shape class."""
        H, W, C = self.spec["input_size"]
        no = self.spec["anchors_per_grid"] * (5 + self.spec["num_classes"])
        out = [("yolov3_tiny", self.yolo,
                (np.zeros((1, H, W, C), np.float32),), "yolov3_tiny")]
        for gh, gw in self.spec["head_grids"]:
            out.append((f"detect_tail_raw {gh}x{gw}", self.tail,
                        (jnp.zeros((1, gh, gw, no), jnp.float32),),
                        "detect_tail_raw"))
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
            self.server.stop()

    # -- correctness -------------------------------------------------------

    def check(self, done: list, seed: int) -> dict:
        """Over the seed's sample of finished requests: the largest
        difference between a served head grid and the reference's, as a
        share of the reference grid's largest magnitude; and the number of
        detect-tail values that differ from the reference tail run on the
        served grid (exact)."""
        lim = self.spec["limits"]
        kept = [r for r in done if r.kept is not None]
        if not kept:
            return {"grid_rel_err": {"value": None,
                                     "limit": lim["grid_rel_err"]},
                    "tail_mismatch": {"value": None,
                                      "limit": lim["tail_mismatch"]}}
        err, mismatch = self.reference_errors(kept)
        return {"grid_rel_err": {"value": err, "limit": lim["grid_rel_err"]},
                "tail_mismatch": {"value": mismatch,
                                  "limit": lim["tail_mismatch"]}}

    def reference_grids(self, j: int, precision: str = "highest"):
        ref = load_module(_REF)
        return [np.asarray(g) for g in ref.grids(
            self.weights, jnp.asarray(self.images[j]), precision=precision,
            layout=(self.spec["rearrange_channels"],
                    tuple(self.spec["pool_strides"]),
                    self.spec["route_layer"], self.spec["leaky_slope"]))]

    def control_error(self) -> float:
        """The control's reading: the reference at ``high`` precision in the
        program's place, over every image of the pool."""
        err = 0.0
        for j in range(len(self.images)):
            for got, w in zip(self.reference_grids(j, "high"),
                              self.reference_grids(j)):
                err = max(err, float(np.abs(got - w).max()
                                     / np.abs(w).max()))
        return err

    def reference_errors(self, kept):
        ref = load_module(_REF)
        det = self.spec["detect"]
        want = {}
        err, mismatch = 0.0, 0
        for r in kept:
            j = self.image_index(r.index)
            if j not in want:
                want[j] = self.reference_grids(j)
            g1, g2, t1, t2 = r.kept
            for got, w in zip((g1, g2), want[j]):
                err = max(err, float(np.abs(got - w).max()
                                     / np.abs(w).max()))
            for grid, tail in ((g1, t1), (g2, t2)):
                exp = ref.detect_tail(grid, det["conf_threshold"],
                                      det["capacity"], det["score_index"])
                mismatch += int((exp != tail).sum())
        return err, mismatch
