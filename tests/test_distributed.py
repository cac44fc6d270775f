"""Multi-device semantics tests: run subprocesses with 8 forced host
devices (XLA_FLAGS must precede jax import, so in-process is not an
option) and verify distributed == single-device results."""
import os
import subprocess
import sys
import textwrap


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_devices(code: str, n_devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nERR:\n{out.stderr}"
    return out.stdout


def test_sharded_moe_matches_dense_ref():
    out = run_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.models.moe import (MoEConfig, moe_params,
                                      moe_block_sharded, moe_block_dense_ref)
        from jax.sharding import AxisType
        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        cfg = MoEConfig(n_experts=8, top_k=2, d_ff=16, n_shared=1,
                        capacity_factor=16.0)   # drop-free
        d = 32
        params = moe_params(jax.random.PRNGKey(0), d, cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, d))
        with mesh:
            out_s, aux_s = jax.jit(
                lambda p, x: moe_block_sharded(p, x, cfg, mesh))(params, x)
        ref = moe_block_dense_ref(params, x, cfg)
        np.testing.assert_allclose(np.asarray(out_s), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)
        assert np.isfinite(float(aux_s))
        print("MOE_OK")
    """)
    assert "MOE_OK" in out


def test_sharded_moe_grads_finite():
    out = run_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.models.moe import (MoEConfig, moe_params,
                                      moe_block_sharded)
        from jax.sharding import AxisType
        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        cfg = MoEConfig(n_experts=8, top_k=2, d_ff=16, capacity_factor=4.0)
        params = moe_params(jax.random.PRNGKey(0), 32, cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, 32))
        def loss(p):
            out, aux = moe_block_sharded(p, x, cfg, mesh)
            return jnp.sum(out ** 2) + aux
        with mesh:
            g = jax.jit(jax.grad(loss))(params)
        ok = all(np.all(np.isfinite(np.asarray(v)))
                 for v in jax.tree_util.tree_leaves(g))
        nz = any(np.any(np.asarray(v) != 0)
                 for v in jax.tree_util.tree_leaves(g))
        assert ok and nz
        print("GRADS_OK")
    """)
    assert "GRADS_OK" in out


def test_lm_train_step_sharded_runs():
    """A reduced MoE train step executes on a real 2x4 mesh with the
    production sharding rules, and loss decreases over steps."""
    out = run_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.steps import build_cell, make_smoke_args
        from repro.launch import sharding as shd
        from jax.sharding import PartitionSpec as P
        from jax.sharding import AxisType
        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        b = build_cell("qwen2-moe-a2.7b", "train_4k", reduced=True)
        args = make_smoke_args(b)
        in_sh = jax.tree.map(lambda s: shd.named(mesh, s),
                             b.sharding_fn(mesh),
                             is_leaf=lambda x: isinstance(x, P))
        with mesh:
            step = jax.jit(b.fn, in_shardings=in_sh,
                           out_shardings=(in_sh[0], in_sh[1], None))
            params, opt, batch, i = jax.tree.map(
                lambda a, s: jax.device_put(a, s), args, in_sh)
            losses = []
            for t in range(8):
                params, opt, loss = step(params, opt, batch,
                                         jnp.asarray(t))
                losses.append(float(loss))
        assert losses[-1] < losses[0]
        print("TRAIN_OK", losses[0], losses[-1])
    """)
    assert "TRAIN_OK" in out


def test_elastic_checkpoint_across_device_counts():
    """Save on 8 devices (2x4 mesh, sharded), restore on 1 device."""
    import tempfile
    with tempfile.TemporaryDirectory() as root:
        run_devices(f"""
            import jax, jax.numpy as jnp, numpy as np
            from jax.sharding import NamedSharding, PartitionSpec as P
            from repro.train.checkpoint import CheckpointManager
            from jax.sharding import AxisType
            mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
            w = jnp.arange(64.0).reshape(8, 8)
            w = jax.device_put(w, NamedSharding(mesh, P("data", "model")))
            CheckpointManager({root!r}).save(5, {{"w": w}})
            print("SAVED")
        """, n_devices=8)
        out = run_devices(f"""
            import jax, jax.numpy as jnp, numpy as np
            from repro.train.checkpoint import CheckpointManager
            tree = {{"w": jnp.zeros((8, 8))}}
            restored, step, _ = CheckpointManager({root!r}).restore(tree)
            np.testing.assert_array_equal(
                np.asarray(restored["w"]),
                np.arange(64.0).reshape(8, 8))
            print("RESTORED", step)
        """, n_devices=1)
        assert "RESTORED 5" in out


def test_retrieval_shard_map_matches_local():
    out = run_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.steps import build_cell
        from repro.launch import sharding as shd
        from jax.sharding import PartitionSpec as P
        from repro.kernels.topk_search.ref import topk_search_ref
        from jax.sharding import AxisType
        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        b = build_cell("fm", "retrieval_cand", reduced=True)
        rng = np.random.default_rng(0)
        n, d = b.arg_specs[0]["candidates"].shape
        cands = rng.standard_normal((n, d)).astype(np.float32)
        cands /= np.linalg.norm(cands, axis=1, keepdims=True)
        q = cands[7:8]
        mask = np.ones(n, bool); mask[-5:] = False
        batch = {"query": jnp.asarray(q),
                 "candidates": jnp.asarray(cands),
                 "candidate_mask": jnp.asarray(mask)}
        fn = b.fn_factory(mesh)
        with mesh:
            s, i = jax.jit(fn)(batch)
        k = s.shape[1]
        s_ref, i_ref = topk_search_ref(jnp.asarray(q), jnp.asarray(cands),
                                       jnp.asarray(mask), k)
        np.testing.assert_allclose(np.asarray(s)[0], np.asarray(s_ref)[0],
                                   rtol=1e-5, atol=1e-5)
        assert int(np.asarray(i)[0, 0]) == 7
        print("RETRIEVAL_OK")
    """)
    assert "RETRIEVAL_OK" in out


def test_gqa_decode_sequence_sharded_matches_ref():
    out = run_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.kernels.flash_decode.ref import decode_attention_ref
        from jax.sharding import AxisType
        mesh = jax.make_mesh((8,), ("model",),
                             axis_types=(AxisType.Auto,))
        rng = np.random.default_rng(0)
        b, h, kv, s, dh = 2, 8, 2, 64, 16
        q = jnp.asarray(rng.standard_normal((b, h, dh)).astype(np.float32))
        kc = jnp.asarray(rng.standard_normal((b, kv, s, dh)).astype(np.float32))
        vc = jnp.asarray(rng.standard_normal((b, kv, s, dh)).astype(np.float32))
        ref = decode_attention_ref(q, kc, vc,
                                   jnp.full((b,), 50, jnp.int32))
        # sequence-sharded cache (the long_500k layout)
        sh = NamedSharding(mesh, P(None, None, "model", None))
        kc_s, vc_s = jax.device_put(kc, sh), jax.device_put(vc, sh)
        with mesh:
            out = jax.jit(decode_attention_ref)(
                q, kc_s, vc_s, jnp.full((b,), 50, jnp.int32))
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)
        print("DECODE_OK")
    """)
    assert "DECODE_OK" in out
