"""Quantized ResNets as FINN builds them: a strided 8-bit stem, bottleneck
blocks of binary-weight 1x1 and 3x3 convs with 2-bit activations, residual
joins re-quantized to 2 bits, a global pool and an 8-bit head.

One configuration file (``bench/configs/<name>.json``) gives the stem, the
stages and the head; this module turns it into

* the float parameters, drawn on the device from ``--seed``, with each
  batchnorm's statistics set from the network's own pre-activations on a
  few images (as training leaves them) and its folded thresholds put
  halfway between two integers of the accumulator (see ``_centre``);
* the raw IR graph, a DAG with the shortcut's fan-out and the joins, that
  ``repro.build`` lowers;
* the images the traffic sends (8-bit RGB, drawn on the device);
* the plain reference: the layer equations in ``jax.numpy`` with exact
  integer accumulation (float32 convolutions of integers whose sums stay
  below 2**24), the batchnorm folded to thresholds in float64 and the head
  in float64 NumPy.  It imports nothing of the program and takes nothing
  it made;
* the algorithm's operations and bytes per kernel call, for the roofline,
  each layer counted at its own stride and padding.

Departures from the published ResNet-50 (He et al., arXiv:1512.03385), as
FINN's quantized ResNet-50 makes them or as this configuration assumes:

* v1.5 strides: a stage's stride sits on the 3x3 conv of its first block
  and on the 1x1 projection shortcut, not on the first 1x1 conv;
* every conv but the stem carries binary (+-1) weights, every activation
  2 bits; the stem and the head take 8-bit weights, the stem 8-bit RGB;
* the last conv of each block is quantized (2-bit, unsigned) before the
  join, as its branch's stream must be integer; the join adds the two
  streams and re-quantizes the sum x to 2 bits as ``floor(x/s + 0.5)``
  clipped to [0, 3], the step ``s`` being ``join_scale`` (ReLU and
  re-quantization in one);
* the global pool sums the 7x7 map (FINN's GlobalAccPool); the 1/49 of an
  average is a constant factor on every logit and is left out;
* the head's logits are its integer accumulator times the weight scale.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

EPS = 1e-5  # batchnorm epsilon, as repro's batchnorm node
ROW_RTOL = 1e-5  # the head's dequant scale in float32 vs float64
CHUNK = 32  # images per reference pass (and per calibration pass)

KERNEL_BY_MODE = {"binary": "conv_mvu_binary", "standard": "conv_mvu_standard"}


@dataclasses.dataclass(frozen=True)
class Step:
    """One node of the network, in dataflow order, with its shapes."""

    name: str
    op: str  # conv | maxpool | join | pool | dense
    inputs: tuple  # producer names ("in" for the image)
    in_shape: tuple  # (H, W, C) or (K,)
    out_shape: tuple
    kernel: int = 1
    stride: int = 1
    pad: int = 0
    mode: str = "binary"  # conv and dense: the weights' datapath
    w_bits: int = 1
    in_range: tuple = (0, 3)  # the integer range of the input stream

    @property
    def k(self) -> int:
        return self.kernel * self.kernel * self.in_shape[-1]

    @property
    def out(self) -> int:
        return self.out_shape[-1]


def out_dim(size: int, kernel: int, stride: int, pad: int) -> int:
    return (size + 2 * pad - kernel) // stride + 1


def walk(spec: dict) -> list[Step]:
    """The configuration's nodes in dataflow order, shapes resolved."""
    top = 2 ** spec["act_bits"] - 1
    h, w, c = spec["input"]["shape"]
    steps: list[Step] = []

    def conv(name, src, shape, out, kernel, stride, pad, in_range,
             mode=spec["mode"], w_bits=spec["weight_bits"]):
        hh, ww, _ = shape
        o = (out_dim(hh, kernel, stride, pad), out_dim(ww, kernel, stride, pad),
             out)
        steps.append(Step(name, "conv", (src,), tuple(shape), o, kernel,
                          stride, pad, mode, w_bits, in_range))
        return o

    st = spec["stem"]
    shape = conv("stem", "in", (h, w, c), st["out"], st["kernel"],
                 st["stride"], st["pad"], (0, 2 ** spec["input"]["bits"] - 1),
                 mode="standard", w_bits=st["weight_bits"])
    p = spec["pool"]
    pooled = (out_dim(shape[0], p["size"], p["stride"], p["pad"]),
              out_dim(shape[1], p["size"], p["stride"], p["pad"]), shape[2])
    steps.append(Step("pool", "maxpool", ("stem",), shape, pooled, p["size"],
                      p["stride"], p["pad"]))
    src, shape = "pool", pooled
    for i, stage in enumerate(spec["stages"], start=1):
        for j in range(stage["blocks"]):
            b = f"s{i}b{j}"
            s = stage["stride"] if j == 0 else 1
            m1 = conv(f"{b}.c1", src, shape, stage["mid"], 1, 1, 0, (0, top))
            m2 = conv(f"{b}.c2", f"{b}.c1", m1, stage["mid"], 3, s, 1, (0, top))
            m3 = conv(f"{b}.c3", f"{b}.c2", m2, stage["out"], 1, 1, 0, (0, top))
            short = src
            if j == 0:  # projection shortcut
                conv(f"{b}.proj", src, shape, stage["out"], 1, s, 0, (0, top))
                short = f"{b}.proj"
            steps.append(Step(f"{b}.join", "join", (f"{b}.c3", short), m3, m3))
            src, shape = f"{b}.join", m3
    pool_top = top * shape[0] * shape[1]
    steps.append(Step("gap", "pool", (src,), shape, (shape[2],)))
    hd = spec["head"]
    steps.append(Step("fc", "dense", ("gap",), (shape[2],), (hd["out"],),
                      mode="standard", w_bits=hd["weight_bits"],
                      in_range=(0, pool_top)))
    return steps


def _compute(steps):
    return [s for s in steps if s.op in ("conv", "dense")]


# ------------------------------------------------------------ parameters
def key_from_seed(seed: int, salt: int):
    """A JAX key from a seed of any size, 64 bits and beyond."""
    words = np.random.SeedSequence([int(seed), salt]).generate_state(2)
    return jax.random.wrap_key_data(words.astype(np.uint32))


def init_params(spec: dict, seed: int):
    """Float parameters of every conv and the head, drawn on the default
    device in one jitted call; then each batchnorm calibrated and centred
    on a few images of the seed (``calibrate``)."""
    layers = _compute(walk(spec))
    ini = spec["init"]

    def draw(key):
        out = {}
        for lay, k in zip(layers, jax.random.split(key, len(layers))):
            kw, kg, kb = jax.random.split(k, 3)
            if lay.op == "conv":
                cin = lay.in_shape[-1]
                w = jax.random.normal(
                    kw, (lay.kernel, lay.kernel, cin, lay.out), jnp.float32)
                n = lay.out
                out[lay.name] = {
                    "w": w,
                    "gamma": jax.random.uniform(kg, (n,), jnp.float32,
                                                *ini["bn_gamma"]),
                    "beta": jax.random.uniform(kb, (n,), jnp.float32,
                                               *ini["bn_beta"]),
                    "mean": jnp.zeros((n,), jnp.float32),
                    "var": jnp.ones((n,), jnp.float32)}
            else:
                out[lay.name] = {"w": jax.random.normal(
                    kw, (lay.out, lay.k), jnp.float32)}
        return out

    params = jax.device_get(jax.jit(draw)(key_from_seed(seed, salt=0)))
    _untie_weights(spec, params)
    calibrate(spec, params, make_inputs(spec, ini["calibration_images"], seed))
    return jax.device_put(params)


def _untie_weights(spec: dict, params: dict) -> None:
    """Move every multi-bit weight off a rounding tie, in place: a weight
    whose quotient by its scale lies within 1e-4 of k + 1/2 (float32
    rounds it either way) shrinks by 3e-4 of the scale."""
    for lay in _compute(walk(spec)):
        if lay.w_bits == 1:
            continue
        w = np.asarray(params[lay.name]["w"], np.float64)
        wm = _as_matrix(w, lay)
        scale = np.maximum(np.max(np.abs(wm), axis=0), 1e-8) / (
            2 ** (lay.w_bits - 1) - 1)
        r = np.abs(wm) / scale
        tie = np.abs(r - np.floor(r) - 0.5) < 1e-4
        wm = np.where(tie, wm - np.sign(wm) * 3e-4 * scale, wm)
        params[lay.name]["w"] = _from_matrix(wm, lay, w.shape).astype(np.float32)


def _as_matrix(w: np.ndarray, lay: Step) -> np.ndarray:
    """(kd, kd, cin, n) conv weights or (n, K) dense weights as (K, N),
    K in (ky, kx, c) order."""
    return w.reshape(lay.k, lay.out) if lay.op == "conv" else w.T


def _from_matrix(wm: np.ndarray, lay: Step, shape: tuple) -> np.ndarray:
    return wm.reshape(shape) if lay.op == "conv" else wm.T


def calibrate(spec: dict, params: dict, x) -> None:
    """Set every batchnorm's mean and variance, in place, to the statistics
    of its pre-activations over ``x`` (node by node, each fed by the ones
    before), as training leaves them; then centre its thresholds
    (``_centre``).  Without this a random draw silences or saturates most
    channels a few blocks in."""
    ref = Reference(spec, params, calibrated=False)
    env = {"in": jnp.asarray(x, jnp.float32)}
    for st in ref.steps[:-1]:
        if st.op != "conv":
            env[st.name] = ref.node(st, env, ref.thr_dev)
            ref.release(st, env)
            continue
        _, scale = ref.weights[st.name]
        acc = conv(env[st.inputs[0]], ref.q_dev[st.name], kernel=st.kernel,
                   stride=st.stride, pad=st.pad)
        p = params[st.name]
        p["mean"], p["var"] = (np.asarray(v, np.float32) for v in _moments(
            acc, jnp.asarray(scale, jnp.float32)))
        _centre(p, scale)
        ref.thr_dev[st.name] = ref.fold(st)
        env[st.name] = levels(acc, ref.thr_dev[st.name])
        ref.release(st, env)


def _centre(p: dict, scale: np.ndarray) -> None:
    """Put the folded thresholds halfway between integers, in place.

    The thresholds sit ``d = std / (gamma * scale)`` accumulator steps
    apart; gamma moves so that d is a whole number, and beta so that the
    first threshold lies at k + 1/2.  The float32 fold the program makes
    then decides every integer accumulator as the float64 fold does: its
    rounding, some 1e-7 of the largest term, stays far below the half step
    (a threshold within float32 rounding of an integer would go either
    way, which neither side gets wrong).  gamma moves by at most half a
    step in d, a fraction of a percent where d is large.
    """
    g = np.asarray(p["gamma"], np.float64)
    std = np.sqrt(np.asarray(p["var"], np.float64) + EPS)
    mean = np.asarray(p["mean"], np.float64)
    d = np.maximum(1.0, np.round(std / (g * scale)))
    p["gamma"] = (std / (scale * d)).astype(np.float32)
    d = std / (np.asarray(p["gamma"], np.float64) * scale)
    t1 = (0.5 - np.asarray(p["beta"], np.float64)) * d + mean / scale
    p["beta"] = (0.5 - (np.floor(t1) + 0.5 - mean / scale) / d).astype(
        np.float32)


def graph(spec: dict, params) -> list:
    """The raw IR DAG ``repro.build.build`` takes: float convs and head,
    batchnorm and ``quant_act`` after every conv, ``add`` then
    ``quant_act`` at each join."""
    from repro.core.ir import Node

    inp = spec["input"]
    g = [Node("input", "in", {"shape": tuple(inp["shape"]), "bits": inp["bits"],
                              "signed": False}, inputs=())]
    act = {"bits": spec["act_bits"], "act_scale": 1.0}
    out = {"in": "in"}  # step name -> the graph node that yields its stream
    for st in walk(spec):
        ins = tuple(out[s] for s in st.inputs)
        if st.op == "conv":
            attrs = {"kernel": st.kernel, "stride": st.stride, "pad": st.pad,
                     "mode": st.mode, "weight_bits": st.w_bits}
            p = params[st.name]
            g.append(Node("conv", st.name, attrs, {"w": p["w"]}, inputs=ins))
            g.append(Node("batchnorm", f"{st.name}.bn", {}, {
                k: p[k] for k in ("gamma", "beta", "mean", "var")},
                inputs=(st.name,)))
            g.append(Node("quant_act", f"{st.name}.act", dict(act),
                          inputs=(f"{st.name}.bn",)))
            out[st.name] = f"{st.name}.act"
        elif st.op == "maxpool":
            g.append(Node("maxpool", st.name, {"size": st.kernel,
                                               "stride": st.stride,
                                               "pad": st.pad}, inputs=ins))
            out[st.name] = st.name
        elif st.op == "join":
            add = st.name.replace(".join", ".add")
            g.append(Node("add", add, {}, inputs=ins))
            g.append(Node("quant_act", st.name,
                          {"bits": spec["act_bits"],
                           "act_scale": float(spec["join_scale"])},
                          inputs=(add,)))
            out[st.name] = st.name
        elif st.op == "pool":
            g.append(Node("global_pool", st.name, {}, inputs=ins))
            out[st.name] = st.name
        else:
            g.append(Node("linear", st.name, {"mode": st.mode,
                                              "weight_bits": st.w_bits},
                          {"w": params[st.name]["w"]}, inputs=ins))
    return g


def build(spec: dict, params):
    """The Accelerator, built the way a user builds it, minus the eager
    per-step verification (``correct`` is decided against the reference)."""
    from repro.build import build as repro_build

    b = spec["build"]
    return repro_build(graph(spec, params), target=b["target"],
                       mode=spec["mode"], weight_bits=spec["weight_bits"],
                       act_bits=spec["act_bits"], folding=b["folding"],
                       verify="off", name=spec["name"])


# ---------------------------------------------------------------- inputs
def make_inputs(spec: dict, n: int, seed: int):
    """``n`` distinct int32 images of unsigned ``bits``-bit pixels, drawn
    on the device from ``seed`` in one jitted call."""
    shape = (n, *spec["input"]["shape"])
    top = 2 ** spec["input"]["bits"]
    draw = jax.jit(lambda k: jax.random.randint(k, shape, 0, top, jnp.int32))
    return draw(key_from_seed(seed, salt=1))


# ------------------------------------------------------------- reference
@functools.partial(jax.jit, static_argnames=("kernel", "stride", "pad"))
def conv(a, q, *, kernel: int, stride: int, pad: int):
    """Exact integer convolution, zero-padded: integer-valued float32
    operands whose products and partial sums stay below 2**24 (8-bit x
    8-bit over the stem's 147 taps at most), at full float32 precision."""
    return jax.lax.conv_general_dilated(
        a, q, (stride, stride), ((pad, pad), (pad, pad)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=jax.lax.Precision.HIGHEST)


@jax.jit
def levels(acc, thr):
    """Levels sum_j (acc >= thr[:, j]) of an integer accumulator, as
    float32; ``thr`` (N, T) holds whole numbers (ceilings of the fold)."""
    return jnp.sum(acc[..., None] >= thr, axis=-1).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("size", "stride", "pad"))
def max_pool(a, *, size: int, stride: int, pad: int):
    """Max over size x size windows; padded cells never win."""
    return jax.lax.reduce_window(
        a, -jnp.inf, jax.lax.max, (1, size, size, 1), (1, stride, stride, 1),
        ((0, 0), (pad, pad), (pad, pad), (0, 0)))


@functools.partial(jax.jit, static_argnames=("step", "top"))
def join(a, b, *, step: float, top: int):
    """The residual join: the sum re-quantized, ``floor(x/step + 0.5)``
    clipped to [0, top] (round half up)."""
    return jnp.clip(jnp.floor((a + b) / step + 0.5), 0, top)


@jax.jit
def sum_pool(a):
    """The global pool: the sum over the map, (B, H, W, C) -> (B, C)."""
    return jnp.sum(a, axis=(1, 2))


@jax.jit
def _moments(acc, scale):
    """Per-channel mean and variance (+1e-3) of the real pre-activations."""
    real = acc * scale
    return (jnp.mean(real, axis=(0, 1, 2)),
            jnp.var(real, axis=(0, 1, 2)) + 1e-3)


class Reference:
    """The plain forward pass.

    Accumulators are exact integers; the batchnorm folds to thresholds and
    the head dequantizes in ``dtype`` (float64 for the reference, bfloat16
    for the control).
    """

    def __init__(self, spec: dict, params_host: dict, *,
                 calibrated: bool = True):
        self.spec = spec
        self.steps = walk(spec)
        self.params = params_host
        self.top = 2 ** spec["act_bits"] - 1
        self.weights = {}  # layer -> (q (K, N) float64, scale (N,) float64)
        self.q_dev = {}  # conv -> q as (kd, kd, cin, n) float32, on device
        self.thr_dev = {}  # conv -> (N, T) float32 thresholds, on device
        for lay in _compute(self.steps):
            self.weights[lay.name] = self._quantize(lay)
            if lay.op == "conv":
                q = self.weights[lay.name][0].reshape(
                    lay.kernel, lay.kernel, lay.in_shape[-1], lay.out)
                self.q_dev[lay.name] = jnp.asarray(q, jnp.float32)
                if calibrated:
                    self.thr_dev[lay.name] = self.fold(lay)
        # the last node that reads each stream, so a pass frees it there
        self.last_use = {s: st.name for st in self.steps for s in st.inputs}

    def _quantize(self, lay: Step):
        wm = _as_matrix(np.asarray(self.params[lay.name]["w"], np.float64),
                        lay)
        if lay.w_bits == 1:
            return np.where(wm >= 0, 1.0, -1.0), np.mean(np.abs(wm), axis=0)
        hi = 2 ** (lay.w_bits - 1) - 1
        scale = np.maximum(np.max(np.abs(wm), axis=0), 1e-8) / hi
        return np.clip(np.round(wm / scale), -hi, hi), scale

    def thresholds(self, lay: Step, dtype=np.float64) -> np.ndarray:
        """(N, T) thresholds on the integer accumulator: level j where
        ``BN(acc * scale) >= j - 1/2``, solved for acc in ``dtype``."""
        p = {k: np.asarray(self.params[lay.name][k]).astype(dtype)
             for k in ("gamma", "beta", "mean", "var")}
        scale = self.weights[lay.name][1].astype(dtype)
        j = np.arange(1, self.top + 1).astype(dtype)[None, :]
        std = np.sqrt(p["var"] + dtype(EPS))[:, None]
        t = ((j - dtype(0.5) - p["beta"][:, None]) * std / p["gamma"][:, None]
             + p["mean"][:, None]) / scale[:, None]
        return np.ceil(np.asarray(t, np.float64))

    def fold(self, lay: Step, dtype=np.float64):
        """:meth:`thresholds` as whole float32 numbers, on the device."""
        return jnp.asarray(self.thresholds(lay, dtype), jnp.float32)

    def node(self, st: Step, env: dict, thr: dict):
        """One non-head node's output levels (float32) from ``env``, with
        the conv thresholds ``thr``."""
        a = env[st.inputs[0]]
        if st.op == "conv":
            acc = conv(a, self.q_dev[st.name], kernel=st.kernel,
                       stride=st.stride, pad=st.pad)
            return levels(acc, thr[st.name])
        if st.op == "maxpool":
            return max_pool(a, size=st.kernel, stride=st.stride, pad=st.pad)
        if st.op == "join":
            return join(a, env[st.inputs[1]],
                        step=float(self.spec["join_scale"]), top=self.top)
        if st.op == "pool":
            return sum_pool(a)
        raise ValueError(f"{st.name}: no level output for op {st.op!r}")

    def release(self, st: Step, env: dict) -> None:
        """Drop from ``env`` the streams ``st`` was the last to read."""
        for src in st.inputs:
            if self.last_use[src] == st.name:
                del env[src]

    def forward(self, x: np.ndarray, *, dtype=np.float64) -> np.ndarray:
        """Logits (B, classes) for the images ``x`` (B, H, W, C)."""
        thr = self.thr_dev if dtype is np.float64 else {
            lay.name: self.fold(lay, dtype)
            for lay in _compute(self.steps) if lay.op == "conv"}
        head = self.steps[-1]
        pooled = []
        for s in range(0, len(x), CHUNK):
            env = {"in": jnp.asarray(np.asarray(x[s:s + CHUNK]), jnp.float32)}
            for st in self.steps[:-1]:
                env[st.name] = self.node(st, env, thr)
                self.release(st, env)
            pooled.append(np.asarray(env[head.inputs[0]], np.float64))
        q, scale = self.weights[head.name]
        acc = np.concatenate(pooled) @ q  # exact: integers below 2**53
        return (acc.astype(dtype) * scale.astype(dtype)).astype(np.float64)

    def judge(self, x: np.ndarray, got: np.ndarray) -> tuple[np.ndarray, dict]:
        """Mask of the rows of ``got`` off the reference by more than the
        float32 rounding of the head's dequant scale, with a note."""
        got = np.asarray(got, np.float64).reshape(len(x), -1)
        ref = self.forward(x)
        tol = ROW_RTOL * np.max(np.abs(ref), axis=1, keepdims=True)
        bad = np.any(~(np.abs(got - ref) <= tol), axis=1)
        return bad, {"rows": int(len(x)), "row_rtol": ROW_RTOL}

    def control(self, x: np.ndarray) -> np.ndarray:
        """The reference one precision down: its float path in bfloat16."""
        import ml_dtypes

        return self.forward(x, dtype=ml_dtypes.bfloat16)


# ------------------------------------------------------------ work counts
def _nbytes(elems: int, bits: int) -> int:
    return -(-elems * bits // 8)


def conv_work(batch: int, st: Step, *, a_bits: int,
              o_bits: int) -> tuple[int, int]:
    """``(ops, bytes)`` of one conv over ``batch`` images at its own stride
    and padding: 2 ops per MAC of each output pixel's window; weights,
    the input map (once, the line buffer's job) and the output map at
    their bit widths."""
    h, w, c = st.in_shape
    oh, ow, n = st.out_shape
    ops = 2 * batch * oh * ow * n * st.k
    nbytes = (_nbytes(n * st.k, st.w_bits) + _nbytes(batch * h * w * c, a_bits)
              + _nbytes(batch * oh * ow * n, o_bits))
    return ops, nbytes


def _bits(lo: int, hi: int) -> int:
    return max(1, int(hi - lo).bit_length())


def _digit_calls(lo: int, hi: int) -> int:
    """Kernel calls the program makes for one layer: one per signed
    base-256 digit its input range needs (``kernels._common.int8_digits``
    on the narrowest dtype holding it)."""
    return 1 if -128 <= lo and hi <= 127 else 2


def kernel_calls(spec: dict, microbatch: int) -> dict[str, list[tuple]]:
    """Kernel name -> ``[(ops, bytes), ...]``, one entry per kernel call in
    one pass over ``microbatch`` images.  A layer whose input is wider than
    int8 runs once per digit; its work is split evenly over those calls,
    so calls over entries still counts passes."""
    act = spec["act_bits"]
    out: dict[str, list[tuple]] = {}
    for st in walk(spec):
        if st.op == "conv":
            kernel = KERNEL_BY_MODE[st.mode]
            work = conv_work(microbatch, st, a_bits=_bits(*st.in_range),
                             o_bits=act)
        elif st.op == "dense":
            kernel = "mvu_int"
            work = (2 * microbatch * st.out * st.k,
                    _nbytes(st.out * st.k, st.w_bits)
                    + _nbytes(microbatch * st.k, _bits(*st.in_range))
                    + _nbytes(microbatch * st.out, 32))
        elif st.op == "join":
            kernel = "add_threshold"
            elems = microbatch * int(np.prod(st.out_shape))
            work = (elems, 3 * _nbytes(elems, act))  # one add per element
        else:
            continue
        calls = _digit_calls(*st.in_range)
        out.setdefault(kernel, []).extend(
            [(work[0] / calls, work[1] / calls)] * calls)
    return out


def ops_per_sample(spec: dict) -> float:
    """The algorithm's operations for one image: 2 per MAC of every conv
    and the head."""
    return float(sum(ops for name, calls in kernel_calls(spec, 1).items()
                     if name != "add_threshold" for ops, _ in calls))
