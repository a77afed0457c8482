"""Multi-device infrastructure tests. These need >1 XLA device, so each runs
in a subprocess with XLA_FLAGS set before jax import (the main pytest
process stays single-device, as the dry-run spec requires)."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(script: str, devices: int = 8, timeout: int = 420) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(script)],
                         capture_output=True, text=True, timeout=timeout,
                         env=env, cwd=REPO)
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    return out.stdout


def test_small_mesh_dryrun_train_and_decode():
    """The dry-run machinery on a small (2,4) virtual mesh with the smoke
    config: lower + compile + roofline extraction end to end."""
    out = _run("""
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_config
        from repro.configs.shapes import ShapeConfig
        from repro.models import build_model, input_specs
        from repro.optim.optimizers import make_optimizer, warmup_cosine
        from repro.train.steps import make_train_step, make_init_state
        from repro.sharding import axes as AX
        from repro.launch.mesh import make_mesh
        from repro.roofline import HloCostModel, roofline_terms

        mesh = make_mesh((2, 4), ("data", "model"))
        rules = {"batch": ("data",), "model": ("model",), "expert": ("data",),
                 "ep_batch": (), "fsdp": (), "seq": ()}
        cfg = get_config("llama3-8b", smoke=True)
        model = build_model(cfg, n_groups=2)
        shape = ShapeConfig("t", "train", 32, 8)
        specs = input_specs(cfg, shape)
        opt = make_optimizer("adamw")
        step = make_train_step(model, opt, warmup_cosine(1e-3, 2, 10),
                               n_microbatches=2)
        with AX.axis_rules(mesh, rules):
            state_shapes = jax.eval_shape(make_init_state(model, opt),
                                          jax.random.PRNGKey(0))
            sds = jax.tree.map(lambda s: jax.ShapeDtypeStruct(
                s.shape, s.dtype), (state_shapes, specs))
            lowered = jax.jit(step).lower(*sds)
        compiled = lowered.compile()
        ma = compiled.memory_analysis()
        cm = HloCostModel(compiled.as_text())
        terms = roofline_terms(cm.entry_cost())
        assert terms["hlo_flops_per_device"] > 0
        assert ma.temp_size_in_bytes > 0
        print("OK", terms["hlo_flops_per_device"])
    """)
    assert "OK" in out


def test_roofline_trip_count_correction():
    """L layers scanned must cost ~L/2 x the 2-layer version (the raw
    cost_analysis would report them equal -- the parser must correct it)."""
    out = _run("""
        import jax, jax.numpy as jnp
        from repro.roofline import HloCostModel

        def make(L):
            def layer(x, w):
                return jnp.tanh(x @ w), None
            def f(ws, x):
                y, _ = jax.lax.scan(layer, x, ws)
                return jnp.sum(y)
            c = jax.jit(f).lower(
                jax.ShapeDtypeStruct((L, 128, 128), jnp.float32),
                jax.ShapeDtypeStruct((64, 128), jnp.float32)).compile()
            return HloCostModel(c.as_text()).entry_cost().flops
        f2, f8 = make(2), make(8)
        ratio = f8 / f2
        assert 3.5 < ratio < 4.5, (f2, f8, ratio)
        print("OK", ratio)
    """, devices=1)
    assert "OK" in out


def test_compressed_allreduce_matches_psum():
    out = _run("""
        import jax, jax.numpy as jnp
        from functools import partial
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        from repro.optim.compression import compressed_psum_mean

        mesh = jax.make_mesh((8,), ("data",))
        g = jax.random.normal(jax.random.PRNGKey(0), (8, 128))
        e = jnp.zeros((8, 128))
        fn = shard_map(partial(compressed_psum_mean, axis_name="data"),
                       mesh=mesh, in_specs=(P("data"), P("data")),
                       out_specs=(P("data"), P("data")))
        mean, err = jax.jit(fn)(g, e)
        exact = jnp.broadcast_to(jnp.mean(g, 0, keepdims=True), g.shape)
        rel = float(jnp.max(jnp.abs(mean - exact)) / jnp.max(jnp.abs(exact)))
        assert rel < 0.05, rel
        # error feedback keeps the long-run average unbiased
        acc = jnp.zeros_like(g); err = jnp.zeros_like(g)
        for _ in range(20):
            m, err = jax.jit(fn)(g, err)
            acc = acc + m
        drift = float(jnp.max(jnp.abs(acc / 20 - exact)))
        assert drift < 0.02 * float(jnp.max(jnp.abs(exact))) + 0.02, drift
        print("OK", rel)
    """)
    assert "OK" in out


def test_checkpoint_restore_resharded():
    """Save on one topology, restore under different shardings (elastic
    restart after losing nodes)."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np, tempfile
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.checkpoint.checkpointer import Checkpointer

        mesh8 = jax.make_mesh((8,), ("d",))
        sh8 = NamedSharding(mesh8, P("d"))
        state = {"w": jax.device_put(jnp.arange(64.0).reshape(8, 8), sh8)}
        d = tempfile.mkdtemp()
        ck = Checkpointer(d)
        ck.save(1, state, blocking=True)

        mesh4 = jax.make_mesh((4, 2), ("d", "m"))
        sh_new = {"w": NamedSharding(mesh4, P("m", "d"))}
        out = ck.restore(jax.eval_shape(lambda: state), shardings=sh_new)
        np.testing.assert_array_equal(np.asarray(out["w"]),
                                      np.arange(64.0).reshape(8, 8))
        assert out["w"].sharding == sh_new["w"]
        print("OK")
    """)
    assert "OK" in out


def test_tcp_worker_protocol():
    """Real head-worker protocol over TCP sockets (paper phases 2-4) with a
    worker subprocess joining via the file rendezvous."""
    out = _run("""
        import subprocess, sys, os, tempfile, threading, time
        from repro.core.cluster import SyndeoCluster
        from repro.core.rendezvous import FileRendezvous
        from repro.core.worker import HeadServer

        rdv_dir = tempfile.mkdtemp()
        cluster = SyndeoCluster(rendezvous=FileRendezvous(rdv_dir))
        server = HeadServer(cluster)
        server.attach()

        env = dict(os.environ)
        env["PYTHONPATH"] = "src"
        worker = subprocess.Popen(
            [sys.executable, "-m", "repro.core.worker", "--role", "worker",
             "--rendezvous", rdv_dir, "--cluster-id", cluster.cluster_id,
             "--max-idle-s", "15"], env=env)
        try:
            deadline = time.time() + 20
            while time.time() < deadline and not any(
                    w.startswith("tcp-") for w in cluster.scheduler.workers):
                time.sleep(0.2)
            assert any(w.startswith("tcp-") for w in cluster.scheduler.workers)
            t = cluster.submit(pow, 2, 10)
            assert cluster.get(t, timeout=30) == 1024
        finally:
            worker.terminate()
            server.shutdown()
            cluster.shutdown()
        print("OK")
    """, devices=1, timeout=180)
    assert "OK" in out
