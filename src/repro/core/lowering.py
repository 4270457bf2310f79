"""Graph transformation passes: FINN's lowering + streamlining, in JAX.

    lower_to_mvu:   conv -> [swu, mvu];  linear -> mvu  (per-node MVUConfig)
    streamline:     [mvu, batchnorm, quant_act] -> mvu(+thresholds)
    fuse_epilogues: same fold for finalized graphs (the runtime engine path),
                    and [add, quant_act] -> add_threshold
    fuse_swu:       [swu, mvu] -> conv_mvu (line-buffer fused conv kernel)
    apply_folding:  attach rate-balanced Folding to every mvu/conv_mvu node
    apply_schedules: pin empirically tuned kernel schedules from the
                     autotune cache onto every mvu/conv_mvu node
    pack_weights:   rewrite packed-datapath nodes' weight storage into the
                    bit-packed form (uint32 bitplanes / uint8 2-bit lanes)

All passes are DAG-aware: patterns match along explicit dataflow edges
(producer -> sole-consumer paths), not list adjacency, so chains and
branched (fan-out/fan-in) graphs rewrite through the same code.  Every
pass returns a graph whose nodes carry explicit ``inputs`` edges.
"""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp

from repro.core import dataflow, ir, swu as swu_mod
from repro.core.folding import balance_pipeline
from repro.core.ir import Graph, Node, validate_graph
from repro.core.mvu import MVUConfig, MVULayer
from repro.core.thresholds import bn_quant_thresholds, streamline_signs


def _reroute(graph: Graph, renames: dict[str, str]) -> Graph:
    """Repoint every input edge through ``renames`` (old producer name ->
    the name of the node that now yields its stream)."""
    if not renames:
        return graph
    out = Graph()
    for n in graph:
        ins = tuple(renames.get(s, s) for s in n.inputs)
        out.append(n if ins == n.inputs else dataclasses.replace(n, inputs=ins))
    return out


def _sole_consumer(cons: dict[str, list[Node]], name: str, op: str) -> Node | None:
    """The single consumer of ``name`` when it exists and has op ``op``."""
    cs = cons.get(name, ())
    if len(cs) == 1 and cs[0].op == op:
        return cs[0]
    return None


def lower_to_mvu(graph: Graph, *, mode: str = "standard",
                 weight_bits: int = 4, act_bits: int = 4,
                 backend: str = "pallas") -> Graph:
    """conv -> swu+mvu; linear -> mvu. Float weights stay attached (raw).

    Every MVU gets its own :class:`MVUConfig`: a conv or linear node's
    ``mode`` / ``weight_bits`` attrs win over the defaults given here, so
    one graph may mix datapaths and bit widths (an 8-bit stem and head
    around a binary body); each output's bits come from its quant_act
    (:func:`streamline`, :func:`fuse_epilogues`)."""
    validate_graph(graph)
    out = Graph()
    renames: dict[str, str] = {}

    def config(node: Node, n: int, k: int) -> MVUConfig:
        a = node.attrs
        return MVUConfig(
            in_features=k, out_features=n, mode=a.get("mode", mode),
            weight_bits=a.get("weight_bits", weight_bits), act_bits=act_bits,
            backend=backend,
        )

    for node in ir.as_graph(graph):
        if node.op == "conv":
            out.append(Node("swu", node.name + ".swu", dict(node.attrs),
                            inputs=node.inputs))
            wm = swu_mod.pack_conv_weights(node.params["w"])  # (N, K)
            out.append(Node("mvu", node.name + ".mvu",
                            {"config": config(node, *wm.shape)},
                            {"w_float": wm}, inputs=(node.name + ".swu",)))
            renames[node.name] = node.name + ".mvu"
        elif node.op == "linear":
            w = node.params["w"]
            out.append(Node("mvu", node.name + ".mvu",
                            {"config": config(node, *w.shape)},
                            {"w_float": w}, inputs=node.inputs))
            renames[node.name] = node.name + ".mvu"
        else:
            out.append(node)
    return _reroute(out, renames)


def streamline(graph: Graph) -> Graph:
    """Fold [mvu, batchnorm, quant_act] into mvu-with-thresholds (MVTU).

    Matched along edges: the batchnorm must be the MVU's sole consumer and
    the quant_act the batchnorm's sole consumer (a fork in between means
    some branch still needs the raw stream).  The quant_act's own fan-out
    is fine -- its consumers are rerouted to the fused node.
    """
    g = ir.as_graph(graph)
    cons = ir.consumer_map(g)
    drop: set[str] = set()
    fused: dict[str, Node] = {}
    renames: dict[str, str] = {}
    for node in g:
        if node.op != "mvu" or "w_float" not in node.params:
            continue
        bn = _sole_consumer(cons, node.name, "batchnorm")
        qa = bn and _sole_consumer(cons, bn.name, "quant_act")
        if qa is None:
            continue
        cfg: MVUConfig = node.attrs["config"]
        w_float = node.params["w_float"]
        bits = qa.attrs["bits"]
        # weight scale factors into BN: acc_int * (w_scale) feeds BN.
        params, qt = MVULayer.from_float(cfg, w_float)
        acc_scale = qt.scale.reshape(-1)  # (N,)
        t, flip = bn_quant_thresholds(
            bn.params["gamma"], bn.params["beta"],
            bn.params["mean"], bn.params["var"],
            bits=bits, acc_scale=1.0,
            act_scale=qa.attrs.get("act_scale", 1.0),
        )
        # thresholds computed against real acc = acc_int * acc_scale:
        t = t / acc_scale[:, None]
        # flip rows (negative gamma): negate quantized weight rows.
        wq = streamline_signs(qt.values.astype(jnp.int32), flip).astype(qt.values.dtype)
        qt2 = type(qt)(wq, qt.scale, qt.bits, qt.signed)
        params, _ = _params_from_qtensor(cfg, qt2, t)
        cfg2 = MVUConfig(**{**cfg.__dict__, "act_bits": bits})
        fused[node.name] = Node("mvu", node.name, {"config": cfg2},
                                {"mvu": params}, inputs=node.inputs)
        drop.update((bn.name, qa.name))
        renames[qa.name] = node.name
    out = Graph(fused.get(n.name, n) for n in g if n.name not in drop)
    return _reroute(out, renames)


def _params_from_qtensor(cfg: MVUConfig, qt, thresholds):
    from repro.core.mvu import MVUParams
    from repro.core.thresholds import integerize_thresholds
    from repro.kernels import packing

    if cfg.mode == "xnor":
        w = packing.pack_bits(packing.bipolar_to_bits(qt.values))
    elif cfg.mode == "binary":
        w = packing.bipolar_to_bits(qt.values).astype(jnp.int8)
    else:
        w = qt.values
    t = integerize_thresholds(thresholds)
    return MVUParams(weights=w, thresholds=t, out_scale=None), qt


def finalize(graph: Graph) -> Graph:
    """Quantize any mvu nodes still carrying float weights (no BN to fold)."""
    out = Graph()
    for node in ir.as_graph(graph):
        if node.op == "mvu" and "mvu" not in node.params:
            cfg: MVUConfig = node.attrs["config"]
            params, _ = MVULayer.from_float(cfg, node.params["w_float"])
            out.append(Node("mvu", node.name, dict(node.attrs), {"mvu": params},
                            inputs=node.inputs))
        else:
            out.append(node)
    return out


def _flip_weight_rows(weights: jnp.ndarray, flip: jnp.ndarray, cfg: MVUConfig):
    """Negate the (bipolar) value of flipped weight rows, per weight coding.

    standard: integer rows negate directly (widened so -(-2^(b-1)) is safe);
    binary:   {0,1}-coded +/-1 rows flip bits (1 - w);
    xnor:     packed rows unpack over the true K bits, flip, repack (pad
              bits stay zero, preserving the popcount correction).
    """
    from repro.kernels import packing

    if cfg.mode == "xnor":
        bits = packing.unpack_bits(weights, cfg.in_features)
        bits = jnp.where(flip[:, None], 1 - bits, bits)
        return packing.pack_bits(bits)
    if cfg.mode == "binary":
        return jnp.where(flip[:, None], 1 - weights, weights).astype(weights.dtype)
    w = streamline_signs(weights.astype(jnp.int32), flip)
    return w.astype(weights.dtype)


def fuse_epilogues(graph: Graph) -> Graph:
    """Fold batchnorm/quant_act successors of *finalized* MVU nodes into the
    kernel's multi-threshold epilogue.

    :func:`streamline` does this rewrite at lowering time on float weights;
    this pass is its runtime-engine analog for graphs that kept standalone
    ``batchnorm``/``quant_act`` nodes (the unfused interpreter path).  The
    dequant scale already attached to the MVU (``out_scale``) folds into the
    thresholds, so the fused node emits integer activation levels straight
    from the accumulator — no float epilogue nodes remain in the hot path.

    Handled patterns (the head MVU and anything else pass through); the
    epilogue nodes must sit on a sole-consumer path off the MVU, while the
    quant_act's own consumers (including residual fan-out) reroute to the
    fused node:
        mvu -> batchnorm -> quant_act   =>  mvu(+thresholds)
        mvu -> quant_act                =>  mvu(+thresholds)  (identity BN)
        add -> quant_act                =>  add_threshold     (residual join)
    """
    from repro.core.mvu import MVUParams

    g = ir.as_graph(graph)
    cons = ir.consumer_map(g)
    drop: set[str] = set()
    fused_nodes: dict[str, Node] = {}
    renames: dict[str, str] = {}
    joins = _fuse_joins(g, cons)
    for node in g:
        if node.name in joins:
            fused_nodes[node.name], qa = joins[node.name]
            drop.add(qa.name)
            renames[qa.name] = node.name
            continue
        fusable = (
            node.op in ("mvu", "conv_mvu")
            and "mvu" in node.params
            and node.params["mvu"].thresholds is None
        )
        if not fusable:
            continue
        bn = _sole_consumer(cons, node.name, "batchnorm")
        qa = (_sole_consumer(cons, bn.name, "quant_act") if bn is not None
              else _sole_consumer(cons, node.name, "quant_act"))
        if qa is None:
            continue

        cfg: MVUConfig = node.attrs["config"]
        params: MVUParams = node.params["mvu"]
        n = cfg.out_features
        bits = qa.attrs["bits"]
        if bn is not None:
            gamma, beta = bn.params["gamma"], bn.params["beta"]
            mean, var = bn.params["mean"], bn.params["var"]
        else:
            # identity BN: var = 1 - eps so sqrt(var + eps) == 1 exactly and
            # the thresholds reduce to the bare quantizer boundaries.
            gamma = jnp.ones((n,), jnp.float32)
            beta = jnp.zeros((n,), jnp.float32)
            mean = jnp.zeros((n,), jnp.float32)
            var = jnp.ones((n,), jnp.float32) - 1e-5
        t, flip = bn_quant_thresholds(
            gamma, beta, mean, var,
            bits=bits, acc_scale=1.0,
            act_scale=qa.attrs.get("act_scale", 1.0),
        )
        # thresholds hold on the real accumulator; the kernel compares the
        # integer accumulator, so divide per-row by the dequant scale.
        scale = params.out_scale
        if scale is not None:
            t = t / scale.reshape(-1)[:, None]
        from repro.core.thresholds import integerize_thresholds

        w = _flip_weight_rows(params.weights, flip, cfg)
        fused_params = MVUParams(
            weights=w, thresholds=integerize_thresholds(t), out_scale=None
        )
        cfg2 = MVUConfig(**{**cfg.__dict__, "act_bits": bits})
        attrs = dict(node.attrs)
        attrs["config"] = cfg2
        attrs["fused"] = tuple(x.name for x in (bn, qa) if x is not None)
        fused_nodes[node.name] = Node(node.op, node.name, attrs,
                                      {"mvu": fused_params}, inputs=node.inputs)
        drop.update(x.name for x in (bn, qa) if x is not None)
        renames[qa.name] = node.name
    out = Graph(fused_nodes.get(n.name, n) for n in g if n.name not in drop)
    return _reroute(out, renames)


def _fuse_joins(g: Graph, cons) -> dict[str, tuple[Node, Node]]:
    """``add -> quant_act`` joins of two integer streams of one shape, as
    ``{add name: (add_threshold node, the quant_act it absorbs)}``.

    For an integer sum x the quantizer's level j (``x >= (j - 0.5) * s``,
    round half up) is ``x >= ceil((j - 0.5) * s)``: integer thresholds,
    the same for every channel."""
    ranges = shapes = None
    out = {}
    for node in g:
        qa = (_sole_consumer(cons, node.name, "quant_act")
              if node.op == "add" else None)
        if qa is None:
            continue
        if ranges is None:
            ranges, shapes = dataflow.int_ranges(g), ir.infer_shapes(g)
        a, b = node.inputs
        scales = tuple(node.attrs.get("scales", (1, 1)))
        if (shapes[a] != shapes[b] or ranges[node.name] is None
                or not all(isinstance(x, int) for x in scales)):
            continue
        bits = qa.attrs["bits"]
        levels = jnp.arange(1, 2**bits, dtype=jnp.float32)
        t = jnp.ceil((levels - 0.5) * qa.attrs.get("act_scale", 1.0))
        n = shapes[node.name][-1]
        thr = jnp.broadcast_to(t.astype(jnp.int32), (n, 2**bits - 1))
        out[node.name] = (Node("add_threshold", node.name,
                               {"scales": scales, "fused": (qa.name,)},
                               {"thresholds": thr}, inputs=node.inputs), qa)
    return out


def fuse_swu(graph: Graph) -> Graph:
    """Collapse ``swu -> mvu`` edges into one ``conv_mvu`` node.

    The standalone SWU materializes the full (B, OH*OW, Kd^2*C) im2col
    matrix in HBM before the MVU consumes it; the fused node streams sliding
    windows through the line-buffer kernel (``kernels.swu_mvu``) instead --
    the runtime analog of FINN's SWU->MVU AXI stream, where the interleaved
    GEMM activation matrix never exists in memory.  Requires finalized MVU
    nodes (``params["mvu"]``) and an SWU with a single consumer; run after
    :func:`finalize` / :func:`fuse_epilogues`.
    """
    g = ir.as_graph(graph)
    cons = ir.consumer_map(g)
    drop: set[str] = set()
    fused: dict[str, Node] = {}
    renames: dict[str, str] = {}
    for node in g:
        if node.op != "swu":
            continue
        mvu = _sole_consumer(cons, node.name, "mvu")
        if mvu is None or "mvu" not in mvu.params:
            continue
        attrs = dict(mvu.attrs)
        attrs["kernel"] = node.attrs["kernel"]
        attrs["stride"] = node.attrs["stride"]
        attrs["pad"] = node.attrs["pad"]
        name = mvu.name.replace(".mvu", ".conv_mvu")
        fused[mvu.name] = Node("conv_mvu", name, attrs, mvu.params,
                               inputs=node.inputs)
        drop.add(node.name)
        renames[mvu.name] = name
    out = Graph(fused.get(n.name, n) for n in g if n.name not in drop)
    return _reroute(out, renames)


def apply_folding(graph: Graph, *, target_cycles: int | None = None,
                  max_pe: int = 128, max_simd: int = 128) -> Graph:
    """FINN folding pass: rate-balance all MVU stages (DESIGN.md section 4).

    Conv stages fold over the pixel dimension too: their cycle count is
    ``n_pixels * NF * SF`` (paper Eq. 1 with the SWU feeding one window per
    output pixel), so a conv layer with few channels but many pixels can
    still be the rate bottleneck.  MVU stages are visited in topological
    (dataflow) order; configs rewrite in place through the shared attrs
    dicts, so the caller's graph is updated.
    """
    shapes = []
    mvu_nodes = []
    for node, _, out_shape in ir.io_shapes(graph):
        if node.op in ("mvu", "conv_mvu"):
            cfg: MVUConfig = node.attrs["config"]
            shapes.append((cfg.out_features, cfg.in_features,
                           ir.n_pixels(out_shape)))
            mvu_nodes.append(node)
    folds = balance_pipeline(shapes, slowest_cycles=target_cycles,
                             max_pe=max_pe, max_simd=max_simd)
    for node, f in zip(mvu_nodes, folds):
        cfg = node.attrs["config"]
        node.attrs["config"] = MVUConfig(**{**cfg.__dict__, "folding": f})
    return graph


def apply_schedules(graph: Graph, *, cache=None, mode: str = "cache",
                    device: str | None = None) -> Graph:
    """Empirical-schedule pass: the autotuned counterpart of ``apply_folding``.

    Rewrites every finalized mvu/conv_mvu node's config with the schedule
    recorded in the autotune cache (``repro.core.autotune``): explicit
    kernel blocks plus the winning backend.  ``mode="cache"`` only consumes
    committed results (zero measurement); ``mode="auto"`` measures misses
    and fills the cache.  Returns a new graph; the input is untouched.
    """
    from repro.core import autotune

    return autotune.tune_graph(graph, cache=cache, mode=mode, device=device)


def pack_weights(graph: Graph, *, force: bool = False) -> Graph:
    """Packing rewrite: store MVU weights in their bit-packed form.

    Rewrites every finalized dense ``mvu`` node whose config selects the
    packed datapath (``cfg.packed`` -- normally pinned by a tuned schedule
    entry carrying ``"packed": true``), or every packable one when
    ``force`` is set (the build's ``pack="always"`` policy).  Storage
    converts per coding: binary {0,1} int8 rows -> uint32 bitplanes (8x
    smaller), standard signed 2-bit rows -> uint8 lanes (4x), xnor rows
    are already uint32 words (storage no-op; the flag still routes the XLA
    backend onto the blocked-popcount path).  Conv nodes keep canonical
    storage -- the fused line-buffer gather consumes unpacked rows.
    Returns a new graph; rewritten nodes carry fresh params/attrs.
    """
    from repro.core.autotune import packable
    from repro.core.mvu import MVUParams
    from repro.kernels.mvu_packed import pack_mvu_weights

    out = Graph()
    for node in graph:
        if node.op != "mvu" or "mvu" not in node.params:
            out.append(node)
            continue
        cfg: MVUConfig = node.attrs["config"]
        if not (cfg.packed or (force and packable(cfg))):
            out.append(node)
            continue
        params = node.params["mvu"]
        w = params.weights
        # idempotence: canonical non-xnor storage is int8 rows; packed
        # forms are uint32 words / uint8 lanes, so dtype tells us whether
        # the rewrite already ran
        if cfg.mode != "xnor" and w.dtype == jnp.int8:
            w = pack_mvu_weights(w, cfg.mode)
        new_params = MVUParams(weights=w, thresholds=params.thresholds,
                               out_scale=params.out_scale)
        new_cfg = (cfg if cfg.packed
                   else MVUConfig(**{**cfg.__dict__, "packed": True}))
        out.append(Node(node.op, node.name,
                        {**node.attrs, "config": new_cfg},
                        {**node.params, "mvu": new_params},
                        inputs=node.inputs))
    return out
