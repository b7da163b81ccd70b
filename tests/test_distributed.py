"""Multi-device semantics on 8 CPU devices (subprocess: the device count
must be set before jax initializes, and other tests need 1 device)."""

import os
import subprocess
import sys
import textwrap

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(body: str):
    code = textwrap.dedent("""
        import sys
        sys.path.insert(0, %r)
        import jax, jax.numpy as jnp, numpy as np
        assert len(jax.devices()) == 8, jax.devices()
    """) % os.path.join(_ROOT, "src") + textwrap.dedent(body)
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"


def test_compressed_grad_sync():
    _run("""
        from repro.distributed.collectives import compressed_grad_sync
        mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
        g = {"a": jnp.arange(8.0), "b": jnp.ones((3, 3))}
        out = compressed_grad_sync(g, mesh)
        np.testing.assert_allclose(np.asarray(out["a"]), np.arange(8.0),
                                   rtol=0.02, atol=0.02)
    """)


def test_ring_allgather_matmul():
    _run("""
        from repro.distributed.collectives import allgather_matmul
        rng = np.random.default_rng(0)
        # n and k must divide the 8-way axis (x k-sharded, w n-sharded)
        x = jnp.asarray(rng.normal(size=(16, 8)).astype(np.float32))
        w = jnp.asarray(rng.normal(size=(8, 16)).astype(np.float32))
        mesh = jax.make_mesh((8,), ("model",))
        y = allgather_matmul(x, w, mesh)
        np.testing.assert_allclose(np.asarray(y), np.asarray(x @ w),
                                   rtol=1e-5, atol=1e-5)
    """)


def test_pipeline_parallel_gpipe():
    _run("""
        from repro.distributed.pipeline import pipeline_apply
        rng = np.random.default_rng(0)
        S, M, mb, dim = 4, 8, 2, 16
        pmesh = jax.make_mesh((4,), ("pipe",))
        Ws = jnp.asarray(rng.normal(size=(S, dim, dim)).astype(np.float32)) * 0.5
        xs = jnp.asarray(rng.normal(size=(M, mb, dim)).astype(np.float32))
        y = pipeline_apply(lambda w, x: jnp.tanh(x @ w), Ws, xs, pmesh,
                           num_microbatches=M)
        ref = xs
        for s in range(S):
            ref = jnp.tanh(ref @ Ws[s])
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)
    """)


def test_sharded_train_step_matches_single_device():
    """The pjit'd train step on a 2x2x2 (pod,data,model) mesh produces the
    same loss/params as single-device execution."""
    _run("""
        from repro.configs import get_config
        from repro.distributed.sharding import use_mesh
        from repro.launch import shardings as shlib
        from repro.launch.mesh import make_mesh
        from repro.models.registry import get_model
        from repro.optim import AdamWConfig, init_adamw
        from repro.train.step import init_train_state, make_train_step
        from repro.nn.module import unbox, axes_of

        cfg = get_config("smollm-135m").reduced(
            num_layers=2, d_model=32, d_ff=64, vocab_size=128,
            num_heads=4, num_kv_heads=2, head_dim=8)
        api = get_model(cfg)
        opt_cfg = AdamWConfig(lr=1e-3)
        rngp = np.random.default_rng(0)
        batch = {
            "tokens": jnp.asarray(rngp.integers(0, 128, (8, 16)).astype(np.int32)),
            "labels": jnp.asarray(rngp.integers(0, 128, (8, 16)).astype(np.int32)),
        }

        params, opt_state, _ = init_train_state(api, opt_cfg,
                                                jax.random.PRNGKey(0))
        step = make_train_step(api, opt_cfg)
        p1, o1, m1 = jax.jit(step)(params, opt_state, batch)

        mesh = make_mesh(2, 2, pods=2)
        with use_mesh(mesh):
            boxed = jax.eval_shape(api.init, jax.random.PRNGKey(0))
            _, psh = shlib.params_shardings(boxed, mesh)
            ost = jax.eval_shape(lambda p: init_adamw(p, opt_cfg), params)
            osh = shlib.opt_shardings(ost, psh, mesh)
            bsh = shlib.batch_shardings(
                {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
                 for k, v in batch.items()}, mesh)
            jstep = jax.jit(step, in_shardings=(psh, osh, bsh),
                            out_shardings=(psh, osh, None))
            pp = jax.device_put(params, psh)
            oo = jax.device_put(opt_state, osh)
            bb = {k: jax.device_put(v, bsh[k]) for k, v in batch.items()}
            p2, o2, m2 = jstep(pp, oo, bb)

        assert abs(float(m1["loss"]) - float(m2["loss"])) < 1e-3, (
            float(m1["loss"]), float(m2["loss"]))
        for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-3, atol=2e-3)
        print("sharded == single-device OK")
    """)


def test_dryrun_single_cell_multipod(tmp_path):
    """A small arch lowers+compiles on the 2x16x16 multi-pod mesh."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch",
         "smollm-135m", "--shape", "decode_32k", "--multi-pod",
         "--out-dir", str(tmp_path)],
        env={**env, "PYTHONPATH": os.path.join(_ROOT, "src")},
        capture_output=True, text=True, timeout=900, cwd=_ROOT)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    assert "[OK]" in r.stdout


def test_launch_train_shards_state_over_the_mesh():
    """launch.train's data+model-parallel path places params across the
    mesh (not all on device 0) and matches the single-device run."""
    _run("""
        from repro.launch import train as train_cli

        def run(dp, mp):
            return train_cli.run(train_cli.parse_args([
                "--arch", "smollm-135m", "--steps", "2", "--batch", "8",
                "--seq", "16", "--data-parallel", str(dp),
                "--model-parallel", str(mp), "--max-restarts", "0"]))

        one, mesh8 = run(1, 1), run(4, 2)
        leaves = jax.tree.leaves(mesh8["params"])
        devs = {d for x in leaves for d in x.sharding.device_set}
        assert len(devs) == 8, devs
        assert any(not x.sharding.is_fully_replicated for x in leaves)
        for a, b in zip(one["history"], mesh8["history"]):
            assert abs(a["loss"] - b["loss"]) < 1e-3, (a, b)
        for a, b in zip(jax.tree.leaves(one["params"]), leaves):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-3, atol=2e-3)
    """)
