"""FINN-style quantized networks: 3x3 conv and dense layers whose
batchnorm and quantized activation fold into thresholds.

One configuration file (``bench/configs/<name>.json``) names the layers,
bit widths and build recipe; this module turns it into

* the float parameters, drawn on the device from ``--seed`` in one jitted
  call, and the raw IR graph that ``repro.build`` lowers (the program's
  own input format: float weights, batchnorm, ``quant_act``);
* the inputs the traffic sends (bit images, or 2-bit flow records);
* the plain reference: the same layers in NumPy, integer accumulators and
  a float64 batchnorm/quantizer, which imports nothing of the program and
  takes nothing it made;
* the algorithm's operations and bytes per kernel call, for the roofline.

Where the exact quantizer decision lies within float32 rounding of the
float64 one (a weight at a rounding tie, a threshold within a few ulp of
an integer), either side is a correct float32 implementation.  Those
decisions are the reference's ``choices``; ``judge`` accepts the program's
outputs when one assignment of them reproduces every output, and
otherwise counts the rows no assignment explains.
"""

from __future__ import annotations

import dataclasses
import itertools

import numpy as np

EPS = 1e-5  # batchnorm epsilon, as repro's batchnorm node
CALIBRATION_ROWS = 1024  # samples whose statistics set a calibrated batchnorm
ROW_RTOL = 1e-5  # the head's dequant scale in float32 vs float64
WEIGHT_TIE = 1e-6  # |w/s| this close to k + 1/2 rounds either way in float32
THRESH_TIE = 1e-5  # relative slack of a float32 threshold against float64
MAX_CHOICES = 6  # reached choices tried: 2**MAX_CHOICES assignments at most

KERNEL_BY_MODE = {("conv", "xnor"): "conv_mvu_xnor", ("dense", "xnor"): "mvu_xnor",
                  ("conv", "standard"): "conv_mvu_int", ("dense", "standard"): "mvu_int"}


@dataclasses.dataclass(frozen=True)
class Layer:
    """One compute layer with its shapes (per sample)."""

    name: str
    op: str  # conv | dense
    in_shape: tuple  # (H, W, C) or (K,)
    out: int
    kernel: int  # 3 for conv, 1 for dense
    head: bool
    in_bits: int
    pe: int | None = None
    simd: int | None = None

    @property
    def k(self) -> int:
        return self.kernel * self.kernel * self.in_shape[-1]


def walk(spec: dict) -> tuple[list, list[Layer]]:
    """The config's layer list with every shape resolved.

    Returns ``(steps, layers)``: ``steps`` is every op in order as
    ``(op, attrs, in_shape)``, ``layers`` the compute layers.
    """
    shape = tuple(spec["input"]["shape"])
    bits = spec["input"]["bits"]
    steps, layers = [], []
    for i, entry in enumerate(spec["layers"]):
        op = entry["op"]
        steps.append((op, entry, shape))
        if op in ("conv", "dense"):
            kd = entry.get("kernel", 3) if op == "conv" else 1
            lay = Layer(f"{op}{i}", op, shape, entry["out"], kd,
                        bool(entry.get("head")), bits,
                        entry.get("pe"), entry.get("simd"))
            layers.append(lay)
            if op == "conv":
                h, w, _ = shape
                shape = (h - kd + 1, w - kd + 1, entry["out"])
            else:
                shape = (entry["out"],)
            bits = spec["act_bits"]
        elif op == "maxpool":
            s = entry["size"]
            h, w, c = shape
            shape = (h // s, w // s, c)
        elif op == "flatten":
            shape = (int(np.prod(shape)),)
        else:
            raise ValueError(f"unknown layer op {op!r} in {spec['name']}")
    return steps, layers


# ------------------------------------------------------------ parameters
def init_params(spec: dict, seed: int):
    """Float parameters of every layer, drawn on the default device in
    one jitted call.  ``seed`` may be any non-negative integer."""
    import jax
    import jax.numpy as jnp

    _, layers = walk(spec)
    ini = spec["init"]

    def draw(key):
        out = {}
        for lay, k in zip(layers, jax.random.split(key, len(layers))):
            kw, kg, kb, km, kv = jax.random.split(k, 5)
            if lay.op == "conv":
                cin = lay.in_shape[-1]
                w = ini["conv_std"] * jax.random.normal(
                    kw, (lay.kernel, lay.kernel, cin, lay.out), jnp.float32)
            else:
                w = jax.random.normal(kw, (lay.out, lay.k), jnp.float32)
                w = w / np.float32(np.sqrt(lay.k))
            p = {"w": w}
            if not lay.head:
                n = lay.out
                p["gamma"] = jax.random.uniform(kg, (n,), jnp.float32,
                                                *ini["bn_gamma"])
                p["beta"] = jax.random.uniform(kb, (n,), jnp.float32,
                                               *ini["bn_beta"])
                if ini.get("bn_stats") == "calibrated":  # set below
                    p["mean"] = jnp.zeros((n,), jnp.float32)
                    p["var"] = jnp.ones((n,), jnp.float32)
                else:
                    p["mean"] = ini["bn_mean_std"] * jax.random.normal(
                        km, (n,), jnp.float32)
                    p["var"] = jax.random.uniform(kv, (n,), jnp.float32,
                                                  *ini["bn_var"])
            out[lay.name] = p
        return out

    params = jax.jit(draw)(key_from_seed(seed, salt=0))
    if ini.get("bn_stats") != "calibrated":
        return params
    host = jax.device_get(params)
    calibrate_bn(spec, host, make_inputs(spec, CALIBRATION_ROWS, seed))
    return jax.device_put(host)


def calibrate_bn(spec: dict, params: dict, x) -> None:
    """Set every batchnorm's mean and variance, in place, to the statistics
    of its pre-activations over ``x`` (layer by layer, each fed by the one
    before), as training leaves its running statistics.  Without this a
    random draw can silence a layer for every input: at 600-64-64-64-1 and
    2 bits some seeds answer 0 to every record."""
    ref = Reference(spec, params)
    a = _as_activations(np.asarray(x), ref.xnor)
    li = 0
    for op, entry, _ in ref.steps:
        if op in ("maxpool", "flatten"):
            a = _reshape_op(a, op, entry)
            continue
        lay = ref.layers[li]
        if lay.head:
            return
        q, scale = ref.weights[lay.name]
        acc = _conv(a, q, lay) if lay.op == "conv" else a @ q
        real = acc.reshape(-1, lay.out).astype(np.float64) * scale
        p = params[lay.name]
        p["mean"] = real.mean(axis=0).astype(np.float32)
        p["var"] = (real.var(axis=0) + 1e-3).astype(np.float32)
        lvl = ref._epilogue(acc, lay, scale, np.float64)
        a = (2 * lvl - 1) if ref.xnor else lvl
        li += 1


def key_from_seed(seed: int, salt: int):
    """A JAX key from a seed of any size, 64 bits and beyond."""
    import jax

    words = np.random.SeedSequence([int(seed), salt]).generate_state(2)
    return jax.random.wrap_key_data(words.astype(np.uint32))


def graph(spec: dict, params) -> list:
    """The raw IR chain ``repro.build.build`` takes."""
    from repro.core.ir import Node

    steps, layers = walk(spec)
    by_name = {lay.name: lay for lay in layers}
    g = [Node("input", "in", {"shape": tuple(spec["input"]["shape"]),
                              "bits": spec["input"]["bits"]})]
    for i, (op, entry, _) in enumerate(steps):
        name = f"{op}{i}"
        if op == "conv":
            g.append(Node("conv", name, {"kernel": by_name[name].kernel,
                                         "stride": 1, "pad": 0},
                          {"w": params[name]["w"]}))
        elif op == "dense":
            g.append(Node("linear", name, {}, {"w": params[name]["w"]}))
        elif op == "maxpool":
            g.append(Node("maxpool", name, {"size": entry["size"]}))
        else:
            g.append(Node("flatten", name, {}))
        if op in ("conv", "dense") and not by_name[name].head:
            p = params[name]
            g.append(Node("batchnorm", f"bn{i}", {}, {
                k: p[k] for k in ("gamma", "beta", "mean", "var")}))
            g.append(Node("quant_act", f"act{i}",
                          {"bits": spec["act_bits"], "act_scale": 1.0}))
    return g


def build(spec: dict, params):
    """The Accelerator, built the way a user builds it, minus the eager
    per-step verification (``correct`` is decided against the reference)."""
    from repro.build import build as repro_build
    from repro.core.folding import Folding

    b = spec["build"]
    _, layers = walk(spec)
    if b["folding"] == "table":
        folding = [Folding(lay.pe, lay.simd) for lay in layers]
    else:
        folding = b["folding"]
    return repro_build(graph(spec, params), target=b["target"],
                       mode=spec["mode"], weight_bits=spec["weight_bits"],
                       act_bits=spec["act_bits"], folding=folding,
                       verify="off", name=spec["name"])


# ---------------------------------------------------------------- inputs
def make_inputs(spec: dict, n: int, seed: int):
    """``n`` distinct int32 samples from ``seed``: flow records on the
    host, bit images on the device (one jitted call)."""
    if "records" in spec:
        return nid_records(n, seed=seed, **spec["records"],
                           n_input=spec["input"]["shape"][0],
                           bits=spec["input"]["bits"])
    import jax
    import jax.numpy as jnp

    shape = (n, *spec["input"]["shape"])
    top = 2 ** spec["input"]["bits"]
    draw = jax.jit(lambda k: jax.random.randint(k, shape, 0, top, jnp.int32))
    return draw(key_from_seed(seed, salt=1))


def nid_records(n: int, *, seed: int, n_raw: int, structure_seed: int,
                n_input: int, bits: int) -> np.ndarray:
    """Synthetic UNSW-NB15-like flow records (a copy of repro's
    ``data/nid.make_dataset``): class-conditional mixtures over ``n_raw``
    base features, projected and quantized to ``n_input`` ``bits``-bit
    features.  ``structure_seed`` fixes the distribution, ``seed`` the
    flows drawn from it."""
    srng = np.random.default_rng(structure_seed)
    proj = srng.normal(0, 1.0, (n_raw, n_input)) / np.sqrt(n_raw)
    centers = srng.normal(0, 1.0, (2, n_raw))
    rng = np.random.default_rng([int(seed), 2])
    y = rng.integers(0, 2, n)
    raw = centers[y] + rng.normal(0, 0.9, (n, n_raw))
    raw[:, :6] += 2.5 * (2 * y[:, None] - 1)
    x = raw @ proj
    x = (x - x.mean(0, keepdims=True)) / (x.std(0, keepdims=True) + 1e-6)
    levels = 2 ** bits - 1
    q = np.clip(np.round((x + 2.0) / 4.0 * levels), 0, levels)
    return q.astype(np.int32)


# ------------------------------------------------------------- reference
class Reference:
    """The plain forward pass in NumPy.

    Accumulators are exact integers (float32 BLAS on values below 2**24);
    dequantization, batchnorm and the quantizer run in ``dtype`` (float64
    for the reference, bfloat16 for the control).
    """

    def __init__(self, spec: dict, params_host: dict):
        self.spec = spec
        self.steps, self.layers = walk(spec)
        self.xnor = spec["mode"] == "xnor"
        self.params = params_host
        self.weights = {}  # layer -> (q (K, N) float32, scale (N,) float64)
        self.choices: list[tuple] = []  # ambiguous quantizer decisions
        for li, lay in enumerate(self.layers):
            self.weights[lay.name] = self._quantize(li, lay)
            if not lay.head:
                self._threshold_choices(li, lay)
        self.choices.sort(key=lambda c: c[-1])  # tightest margin first

    def _quantize(self, li: int, lay: Layer):
        w = np.asarray(self.params[lay.name]["w"], np.float64)
        if lay.op == "conv":  # (kd, kd, cin, n) -> (K, N) in (ky, kx, c) order
            wm = w.reshape(lay.k, lay.out)
        else:
            wm = w.T
        bits = 1 if self.xnor else self.spec["weight_bits"]
        if bits == 1:
            q = np.where(wm >= 0, 1.0, -1.0)
            scale = np.mean(np.abs(wm), axis=0)
            return q.astype(np.float32), scale
        hi = 2 ** (bits - 1) - 1
        scale = np.maximum(np.max(np.abs(wm), axis=0), 1e-8) / hi
        r = wm / scale
        q = np.clip(np.round(r), -hi, hi)
        frac = np.abs(r) - np.floor(np.abs(r))
        for kk, nn in zip(*np.nonzero(np.abs(frac - 0.5) < WEIGHT_TIE)):
            down = np.sign(r[kk, nn]) * np.floor(np.abs(r[kk, nn]))
            alt = down + np.sign(r[kk, nn]) if q[kk, nn] == down else down
            self.choices.append(("w", li, int(kk), int(nn),
                                 float(np.clip(alt, -hi, hi)),
                                 abs(frac[kk, nn] - 0.5) / WEIGHT_TIE))
        return q.astype(np.float32), scale

    def _threshold_choices(self, li: int, lay: Layer):
        p = {k: np.asarray(self.params[lay.name][k], np.float64)
             for k in ("gamma", "beta", "mean", "var")}
        _, scale = self.weights[lay.name]
        std = np.sqrt(p["var"] + EPS)
        for j in range(1, 2 ** self.spec["act_bits"]):
            a = (j - 0.5 - p["beta"]) * std / p["gamma"]
            t = (a + p["mean"]) / scale
            slack = THRESH_TIE * (1 + np.abs(t) +
                                  (np.abs(a) + np.abs(p["mean"])) / scale)
            near = np.round(t)
            for c in np.nonzero(np.abs(t - near) <= slack)[0]:
                self.choices.append(("t", li, int(c), j, int(near[c]),
                                     abs(t[c] - near[c]) / slack[c]))

    def forward(self, x: np.ndarray, *, dtype=np.float64,
                flip: frozenset = frozenset(), hits: set | None = None
                ) -> np.ndarray:
        """Outputs for ``x`` (B, *input shape).  ``flip`` holds the indices
        of ``choices`` decided the other way; ``hits``, when given,
        collects the indices of the choices some row of ``x`` reaches."""
        a = _as_activations(x, self.xnor)
        li = 0
        for op, entry, _ in self.steps:
            if op in ("maxpool", "flatten"):
                a = _reshape_op(a, op, entry)
                continue
            lay = self.layers[li]
            mine = [(i, c) for i, c in enumerate(self.choices) if c[1] == li]
            q, scale = self.weights[lay.name]
            if any(i in flip for i, c in mine if c[0] == "w"):
                q = q.copy()
                for i, (_, _, kk, nn, alt, _) in mine:
                    if i in flip and self.choices[i][0] == "w":
                        q[kk, nn] = alt
            acc = _conv(a, q, lay) if lay.op == "conv" else a @ q
            if hits is not None:
                hits.update(i for i, c in mine if c[0] == "w")
            if lay.head:
                return (acc.astype(dtype) * scale.astype(dtype)).astype(np.float64)
            lvl = self._epilogue(acc, lay, scale, dtype)
            for i, (kind, _, ch, j, near, _) in mine:
                if kind != "t":
                    continue
                at = acc[..., ch] == near
                if hits is not None and at.any():
                    hits.add(i)
                if i in flip:
                    lvl[..., ch] = np.where(at & (lvl[..., ch] == j), j - 1,
                                            np.where(at, j, lvl[..., ch]))
            a = (2 * lvl - 1) if self.xnor else lvl
            li += 1
        raise ValueError(f"{self.spec['name']}: no head layer")

    def _epilogue(self, acc, lay: Layer, scale, dtype):
        """Batchnorm and quantizer in ``dtype``, tabulated over every
        integer the accumulator can take and then looked up."""
        p = {k: np.asarray(self.params[lay.name][k]).astype(dtype)
             for k in ("gamma", "beta", "mean", "var")}
        lo, hi = int(acc.min()), int(acc.max())
        grid = np.arange(lo, hi + 1, dtype=np.float64)[:, None].astype(dtype)
        real = grid * scale.astype(dtype)
        std = np.sqrt(p["var"] + dtype(EPS))
        y = (real - p["mean"]) * p["gamma"] / std + p["beta"]
        top = 2 ** self.spec["act_bits"] - 1
        table = np.clip(np.floor(y + dtype(0.5)), 0, top).astype(np.float32)
        return table[acc.astype(np.int64) - lo, np.arange(lay.out)]

    def judge(self, x: np.ndarray, got: np.ndarray) -> tuple[np.ndarray, dict]:
        """Mask of the rows of ``got`` that no assignment of the reached
        ``choices`` reproduces, with a note of the assignment used."""
        got = np.asarray(got, np.float64).reshape(len(x), -1)
        hits: set = set()
        bad = _bad_rows(got, self.forward(x, hits=hits))
        note = {"choices": len(self.choices), "reached": len(hits),
                "flipped": []}
        if not bad.any() or not hits:
            return bad, note
        idx = np.nonzero(bad)[0]
        pool = sorted(hits, key=lambda i: self.choices[i][-1])[:MAX_CHOICES]
        best = (int(bad.sum()), frozenset())
        for r in range(1, len(pool) + 1):
            for flip in map(frozenset, itertools.combinations(pool, r)):
                n = int(_bad_rows(got[idx], self.forward(x[idx], flip=flip)).sum())
                if n < best[0]:
                    best = (n, flip)
        if best[1]:
            bad_v = _bad_rows(got, self.forward(x, flip=best[1]))
            if bad_v.sum() < bad.sum():
                note["flipped"] = [list(self.choices[i][:5]) for i in best[1]]
                return bad_v, note
        return bad, note

    def control(self, x: np.ndarray) -> np.ndarray:
        """The reference one precision down: its float path in bfloat16."""
        import ml_dtypes

        return self.forward(x, dtype=ml_dtypes.bfloat16)


def _as_activations(x, xnor: bool) -> np.ndarray:
    """Input levels as matmul operands: bits to +-1 for the xnor datapath."""
    a = np.asarray(x, np.float32)
    return 2 * a - 1 if xnor else a


def _reshape_op(a: np.ndarray, op: str, entry: dict) -> np.ndarray:
    if op == "flatten":
        return a.reshape(a.shape[0], -1)
    s = entry["size"]  # max-pool, stride = size, valid
    b, h, w, c = a.shape
    return a[:, :h // s * s, :w // s * s].reshape(
        b, h // s, s, w // s, s, c).max(axis=(2, 4))


def _conv(a: np.ndarray, q: np.ndarray, lay: Layer, block: int = 64
          ) -> np.ndarray:
    """Valid stride-1 conv as im2col (in (ky, kx, c) order) and one
    matmul per block of images, exact for integers."""
    from numpy.lib.stride_tricks import sliding_window_view

    b, h, w, c = a.shape
    kd = lay.kernel
    oh, ow = h - kd + 1, w - kd + 1
    out = np.empty((b, oh, ow, lay.out), np.float32)
    for s in range(0, b, block):
        win = sliding_window_view(a[s:s + block], (kd, kd), axis=(1, 2))
        cols = win.transpose(0, 1, 2, 4, 5, 3).reshape(-1, lay.k)
        out[s:s + block] = (cols @ q).reshape(-1, oh, ow, lay.out)
    return out


def _bad_rows(got: np.ndarray, ref: np.ndarray) -> np.ndarray:
    """Rows whose outputs differ from the reference by more than the
    float32 rounding of the head's dequant scale (relative to the row)."""
    ref = ref.reshape(got.shape)
    tol = ROW_RTOL * np.max(np.abs(ref), axis=1, keepdims=True)
    return np.any(~(np.abs(got - ref) <= tol), axis=1)


# ------------------------------------------------------------ work counts
def kernel_calls(spec: dict, microbatch: int) -> dict[str, list[tuple]]:
    """Kernel name -> ``[(ops, bytes), ...]``, one entry per layer, for one
    kernel call on ``microbatch`` samples (the algorithm's work: 2 ops per
    MAC; weights, inputs and outputs at their bit widths)."""
    from bench.roofline import work

    _, layers = walk(spec)
    wbits = 1 if spec["mode"] == "xnor" else spec["weight_bits"]
    out: dict[str, list[tuple]] = {}
    for lay in layers:
        obits = 32 if lay.head else spec["act_bits"]
        if lay.op == "conv":
            h, w, c = lay.in_shape
            ops, nbytes = work.conv(microbatch, h, w, c, lay.out, lay.kernel,
                                    w_bits=wbits, a_bits=lay.in_bits,
                                    o_bits=obits)
        else:
            ops, nbytes = work.dense(microbatch, lay.out, lay.k, w_bits=wbits,
                                     a_bits=lay.in_bits, o_bits=obits)
        out.setdefault(KERNEL_BY_MODE[(lay.op, spec["mode"])], []).append(
            (ops, nbytes))
    return out


def ops_per_sample(spec: dict) -> float:
    """The algorithm's operations for one sample, all layers."""
    return float(sum(ops for calls in kernel_calls(spec, 1).values()
                     for ops, _ in calls))
