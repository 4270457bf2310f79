"""Distribution tests: run in subprocesses with forced host device counts
so the main pytest process keeps a single CPU device (per the dry-run
contract: XLA_FLAGS is never set globally)."""

import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_devices(n: int, body: str, timeout: int = 600) -> str:
    code = textwrap.dedent(
        f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={n}"
        """
    ) + textwrap.dedent(body)
    proc = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True,
        text=True,
        timeout=timeout,
        env={**os.environ, "PYTHONPATH": os.path.join(REPO, "src")},
    )
    assert proc.returncode == 0, f"STDOUT:\n{proc.stdout}\nSTDERR:\n{proc.stderr}"
    return proc.stdout


def test_sharded_train_step_runs_and_matches_single_device():
    out = run_devices(8, """
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_reduced
        from repro.models.model import build
        from repro.launch.mesh import make_mesh
        from repro.launch.train import shard_train_step, init_sharded
        from repro.optim import adamw

        cfg = get_reduced("yi-9b").replace(dtype="float32", remat=False)
        model = build(cfg)
        opt_cfg = adamw.AdamWConfig(lr=1e-3, warmup_steps=1, total_steps=10)
        batch = {"tokens": jax.random.randint(jax.random.PRNGKey(9), (8, 33), 0, cfg.vocab_size)}

        mesh = make_mesh((4, 2))
        step, p_sh, o_sh, b_sh = shard_train_step(model, mesh, opt_cfg, batch)
        params, opt_state, _, _ = init_sharded(model, mesh)
        p0 = jax.tree.map(lambda x: np.asarray(x).copy(), params)
        b = jax.device_put(batch, b_sh)
        params, opt_state, metrics = step(params, opt_state, b)
        l_mesh = float(metrics["loss"])

        # single-device reference
        params1 = model.init(jax.random.PRNGKey(0))
        opt1 = adamw.init(params1)
        from repro.launch.train import make_train_step
        params1, opt1, m1 = make_train_step(model, opt_cfg)(params1, opt1, batch)
        l_single = float(m1["loss"])
        assert abs(l_mesh - l_single) < 1e-3, (l_mesh, l_single)

        # params actually moved and match the single-device update
        moved = sum(float(np.abs(np.asarray(a) - b0).max()) for a, b0 in
                    zip(jax.tree.leaves(params), jax.tree.leaves(p0)))
        assert moved > 0
        err = max(float(np.abs(np.asarray(a) - np.asarray(b1)).max())
                  for a, b1 in zip(jax.tree.leaves(params), jax.tree.leaves(params1)))
        assert err < 5e-3, err
        print("OK", l_mesh, l_single, err)
    """)
    assert "OK" in out


def test_moe_and_hybrid_shard_on_mesh():
    out = run_devices(8, """
        import jax, jax.numpy as jnp
        from repro.configs import get_reduced
        from repro.models.model import build
        from repro.launch.mesh import make_mesh
        from repro.launch.train import shard_train_step, init_sharded
        from repro.optim import adamw

        for arch in ["granite-moe-3b-a800m", "jamba-1.5-large-398b"]:
            cfg = get_reduced(arch).replace(dtype="float32")
            model = build(cfg)
            mesh = make_mesh((2, 4))
            batch = {"tokens": jax.random.randint(jax.random.PRNGKey(0), (4, 17), 0, cfg.vocab_size)}
            step, p_sh, o_sh, b_sh = shard_train_step(
                model, mesh, adamw.AdamWConfig(total_steps=5), batch)
            params, opt_state, _, _ = init_sharded(model, mesh)
            params, opt_state, metrics = step(params, opt_state, jax.device_put(batch, b_sh))
            assert jnp.isfinite(metrics["loss"]), arch
            print("OK", arch, float(metrics["loss"]))
    """)
    assert out.count("OK") == 2


def test_serve_fns_shard_and_decode():
    out = run_devices(8, """
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_reduced
        from repro.models.model import build
        from repro.launch.mesh import make_mesh
        from repro.launch.serve import shard_serve_fns

        cfg = get_reduced("yi-9b").replace(dtype="float32")
        model = build(cfg)
        mesh = make_mesh((4, 2))
        B, L = 8, 64
        prefill, decode, p_sh, s_sh = shard_serve_fns(model, mesh, B, L)
        params = jax.jit(model.init, out_shardings=p_sh)(jax.random.PRNGKey(0))
        state = jax.jit(lambda: model.init_decode_state(B, L), out_shardings=s_sh)()
        toks = jax.random.randint(jax.random.PRNGKey(1), (B, 16), 0, cfg.vocab_size)
        logits, state = prefill(params, {"tokens": toks}, state)
        for _ in range(4):
            logits, state = decode(params, state, jnp.argmax(logits, -1))
        assert logits.shape == (B, cfg.vocab_size)
        assert bool(jnp.all(jnp.isfinite(logits)))
        print("OK")
    """)
    assert "OK" in out


def test_checkpoint_elastic_rescale():
    out = run_devices(8, """
        import tempfile, jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_reduced
        from repro.models.model import build
        from repro.launch.mesh import make_mesh
        from repro.distributed.sharding import param_shardings
        from repro.checkpoint import ckpt

        cfg = get_reduced("yi-9b").replace(dtype="float32")
        model = build(cfg)
        mesh_a = make_mesh((2, 4))
        shape = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        params = jax.jit(model.init, out_shardings=param_shardings(mesh_a, shape))(
            jax.random.PRNGKey(0))
        with tempfile.TemporaryDirectory() as d:
            ckpt.save(d, 7, params)
            assert ckpt.latest_step(d) == 7
            # restore onto a *different* mesh shape (elastic rescale)
            mesh_b = make_mesh((8, 1))
            restored = ckpt.restore(d, 7, shape, param_shardings(mesh_b, shape))
            err = max(float(jnp.abs(a - b).max()) for a, b in
                      zip(jax.tree.leaves(params), jax.tree.leaves(restored)))
            assert err == 0.0, err
        print("OK")
    """)
    assert "OK" in out


def test_pipeline_parallel_matches_sequential():
    out = run_devices(4, """
        import jax, jax.numpy as jnp, numpy as np
        from repro.distributed.pipeline import (
            pipeline_apply, sequential_reference, stage_params_split)
        from repro.launch.mesh import make_mesh

        L, S, n_micro, mb, d = 8, 4, 8, 4, 16
        key = jax.random.PRNGKey(0)
        w = jax.random.normal(key, (L, d, d)) * (1.0 / np.sqrt(d))
        b = jnp.zeros((L, d))
        params = {"w": w, "b": b}
        x = jax.random.normal(jax.random.PRNGKey(1), (n_micro, mb, d))

        def layer_fn(p, h):
            return jnp.tanh(h @ p["w"] + p["b"])

        mesh = make_mesh((S,), ("stage",))
        got = pipeline_apply(layer_fn, stage_params_split(params, S), x, mesh)
        want = sequential_reference(layer_fn, params, x)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)

        # gradients flow through the pipeline schedule
        def loss(params):
            y = pipeline_apply(layer_fn, stage_params_split(params, S), x, mesh)
            return jnp.sum(y ** 2)
        g = jax.grad(loss)(params)
        def loss_ref(params):
            return jnp.sum(sequential_reference(layer_fn, params, x) ** 2)
        g_ref = jax.grad(loss_ref)(params)
        np.testing.assert_allclose(np.asarray(g["w"]), np.asarray(g_ref["w"]),
                                   rtol=1e-4, atol=1e-4)
        print("OK")
    """)
    assert "OK" in out


def test_grad_compression_converges():
    out = run_devices(4, """
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.launch.mesh import make_mesh
        from repro.optim import grad_compress as gc

        mesh = make_mesh((4,), ("data",))
        # toy regression, data-parallel over 4 devices
        k = jax.random.PRNGKey(0)
        w_true = jax.random.normal(k, (16,))
        X = jax.random.normal(jax.random.PRNGKey(1), (64, 16))
        y = X @ w_true

        def local_grad(w, xb, yb):
            return jax.grad(lambda w: jnp.mean((xb @ w - yb) ** 2))(w)

        # each data shard keeps its own error-feedback residual
        w = jnp.zeros((16,))
        errs = jnp.zeros((4, 16))

        @jax.jit
        def step(w, errs, X, y):
            def inner(w, e, xb, yb):
                g = local_grad(w, xb, yb)
                gg, e2 = gc.psum_compressed({"w": g}, {"w": e[0]}, ("data",))
                return gg["w"], e2["w"][None]
            g, errs = jax.shard_map(inner, mesh=mesh,
                in_specs=(P(), P("data"), P("data"), P("data")),
                out_specs=(P(), P("data")), check_vma=False)(w, errs, X, y)
            return w - 0.2 * g, errs

        for i in range(200):
            w, errs = step(w, errs, X, y)
        final = float(jnp.mean((X @ w - y) ** 2))
        assert final < 1e-3, final
        print("OK", final)
    """)
    assert "OK" in out


def test_as_pipeline_rejects_unstackable_graphs():
    """FusedEngine.as_pipeline error paths (the happy path runs in
    tests/test_engine.py): heterogeneous ops, heterogeneous MVU shapes,
    mixed epilogue forms, and the xnor packed-width rejection must all fail
    with clear errors before any device work happens."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core import lowering
    from repro.core.engine import FusedEngine
    from repro.core.ir import Node
    from repro.launch.mesh import make_mesh

    rng = np.random.default_rng(31)
    mesh = make_mesh((1,), ("stage",))

    def mlp(dims, bits, with_bn=True):
        g = [Node("input", "in", {"shape": (dims[0],), "bits": bits})]
        for i, (k, n) in enumerate(zip(dims[:-1], dims[1:])):
            w = rng.normal(0, 0.5, (n, k)).astype(np.float32)
            g.append(Node("linear", f"fc{i}", {}, {"w": jnp.asarray(w)}))
            if with_bn and i < len(dims) - 2:
                g.append(Node("batchnorm", f"bn{i}", {}, {
                    "gamma": jnp.asarray(rng.uniform(0.5, 1.5, n).astype(np.float32)),
                    "beta": jnp.asarray(rng.uniform(-0.5, 0.5, n).astype(np.float32)),
                    "mean": jnp.asarray(rng.normal(0, 1, n).astype(np.float32)),
                    "var": jnp.asarray(rng.uniform(0.5, 2, n).astype(np.float32)),
                }))
                g.append(Node("quant_act", f"act{i}",
                              {"bits": bits, "act_scale": 1.0}))
        return g

    def engine(dims, mode, bits, **kw):
        fin = lowering.finalize(lowering.lower_to_mvu(
            mlp(dims, bits, **kw), mode=mode, weight_bits=4, act_bits=bits))
        return FusedEngine(fin)

    # heterogeneous ops: a conv graph keeps a conv_mvu node in the chain
    g = [Node("input", "in", {"shape": (6, 6, 3), "bits": 2}),
         Node("conv", "c0", {"kernel": 3, "stride": 1, "pad": 0},
              {"w": jnp.asarray(rng.normal(0, 0.5, (3, 3, 3, 4)).astype(np.float32))})]
    conv_engine = FusedEngine(lowering.finalize(
        lowering.lower_to_mvu(g, mode="standard", weight_bits=4, act_bits=2)))
    with pytest.raises(ValueError, match="pure MVU chain"):
        conv_engine.as_pipeline(mesh)

    # heterogeneous (N, K) stage shapes cannot stack into one layer_fn
    with pytest.raises(ValueError, match="homogeneous"):
        engine([24, 16, 8], "standard", 2).as_pipeline(mesh)

    # xnor stages: the static packed width breaks parameter stacking
    with pytest.raises(ValueError, match="xnor"):
        engine([32, 32, 32], "xnor", 1).as_pipeline(mesh)

    # mixed epilogue forms: hidden stage carries fused thresholds, the head
    # runs raw accumulators -- stacking would silently change semantics
    mixed = engine([16, 16, 16], "standard", 2)
    mvus = [n for n in mixed.graph if n.op == "mvu"]
    assert mvus[0].params["mvu"].thresholds is not None
    assert mvus[-1].params["mvu"].thresholds is None
    with pytest.raises(ValueError, match="epilogue"):
        mixed.as_pipeline(mesh)


def test_dryrun_cell_lowers_on_host_mesh():
    """The dry-run cell builder (shardings + lower + compile + cost) works
    on a small host mesh with a reduced config — CI-scale proof of the
    sharding rules used by the 256/512-chip meshes."""
    out = run_devices(8, """
        import jax
        jax.devices()  # lock 8 host devices before importing dryrun
        from repro.launch import dryrun
        from repro.configs import get_reduced
        from repro.launch.mesh import make_mesh

        mesh = make_mesh((4, 2))
        for arch in ["yi-9b", "granite-moe-3b-a800m", "mamba2-780m"]:
            cfg = get_reduced(arch)
            fn, args, donate, shardings, cfg, acct = dryrun.build_cell(
                cfg, "train_4k", mesh)
            with jax.set_mesh(mesh):
                compiled = jax.jit(fn, in_shardings=shardings,
                                   donate_argnums=donate).lower(*args).compile()
            cost = compiled.cost_analysis()
            if isinstance(cost, list):
                cost = cost[0]
            assert cost.get("flops", 0) > 0
            coll = dryrun.parse_collective_bytes(
                compiled.as_text(), dryrun.scan_trip_count(cfg))
            print("OK", arch, int(coll["total_bytes"]))
    """, timeout=900)
    assert out.count("OK") == 3
