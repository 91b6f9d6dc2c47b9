"""Multi-device semantics (8 forced host devices in a subprocess): MoE
shard_map parity, mesh-independence of the full models, sharded ABA,
compressed data-parallel training.

These run as subprocesses because jax pins the device count at first init
and the main pytest process must keep seeing exactly one CPU device.
"""

import subprocess
import sys
import textwrap

import pytest


def _run(body: str, devices: int = 8, timeout: int = 900):
    code = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={devices}"
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        import sys
        sys.path.insert(0, "src")
    """) + textwrap.dedent(body)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=timeout, cwd=__file__.rsplit(
                           "/tests/", 1)[0])
    assert r.returncode == 0, f"STDOUT:{r.stdout}\nSTDERR:{r.stderr[-3000:]}"
    return r.stdout


@pytest.mark.slow
def test_mesh_independence_moe_archs():
    out = _run("""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import Mesh
        from repro.models.registry import get_config
        from repro.models import transformer as T
        key = jax.random.PRNGKey(0)
        for arch in ("jamba-v0.1-52b", "granite-moe-3b-a800m"):
            cfg = get_config(arch, reduced=True)
            params = T.init_params(cfg, key)
            tokens = jax.random.randint(key, (4, 16), 0, cfg.vocab_size)
            m1 = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
            m2 = Mesh(np.array(jax.devices()).reshape(2, 4), ("data", "model"))
            l1 = np.asarray(T.forward(cfg, params, tokens, mesh=m1))
            with m2:
                l2 = np.asarray(jax.jit(lambda p, t: T.forward(cfg, p, t, mesh=m2))(params, tokens))
            err = float(np.abs(l1 - l2).max())
            assert err < 1e-3, (arch, err)
            print(arch, "ok", err)
    """)
    assert out.count("ok") == 2


@pytest.mark.slow
def test_sharded_aba_matches_local_hierarchy():
    out = _run("""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from repro.core.sharded import sharded_aba
        from repro.core.objective import balance_ok, objective_centroid
        from repro.core.baselines import random_partition
        mesh = Mesh(np.array(jax.devices()).reshape(4, 2), ("data", "model"))
        rng = np.random.default_rng(0)
        x = rng.normal(size=(512, 6)).astype(np.float32)
        with mesh:
            xs = jax.device_put(x, NamedSharding(mesh, P("data", None)))
            labels = np.asarray(sharded_aba(xs, 16, mesh, data_axes=("data",)))
        assert balance_ok(labels, 16, 512)
        o = float(objective_centroid(jnp.asarray(x), jnp.asarray(labels), 16))
        lr = random_partition(512, 16, seed=0)
        orr = float(objective_centroid(jnp.asarray(x), jnp.asarray(lr), 16))
        assert o > orr * 0.999, (o, orr)
        # per-shard locality: rows of shard s only get labels [s*4, s*4+4)
        for s in range(4):
            seg = labels[s * 128:(s + 1) * 128]
            assert seg.min() >= s * 4 and seg.max() < (s + 1) * 4
        print("ok", o, orr)
    """)
    assert "ok" in out


@pytest.mark.slow
def test_compressed_dp_training():
    out = _run("""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import Mesh
        from repro.models.registry import get_config
        from repro.models import transformer as T
        from repro.train.optimizer import OptConfig, adamw_init
        from repro.train.compression import (init_error_state,
                                             make_compressed_dp_train_step)
        cfg = get_config("smollm-360m", reduced=True)
        key = jax.random.PRNGKey(0)
        params = T.init_params(cfg, key)
        mesh = Mesh(np.array(jax.devices()).reshape(8, 1), ("data", "model"))
        step = jax.jit(make_compressed_dp_train_step(
            cfg, mesh, OptConfig(lr=3e-3, warmup_steps=2, decay_steps=20),
            loss_chunk=8))
        opt = adamw_init(params)
        err = init_error_state(params)
        tokens = jax.random.randint(key, (32, 32), 0, cfg.vocab_size)
        losses = []
        with mesh:
            for i in range(12):
                params, opt, err, m = step(params, opt, err,
                                           {"tokens": tokens})
                losses.append(float(m["loss"]))
        assert all(np.isfinite(losses))
        assert losses[-1] < losses[0] - 0.3, losses
        print("ok", losses[0], losses[-1])
    """)
    assert "ok" in out


@pytest.mark.slow
def test_ef_compression_error_bounded():
    out = _run("""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import Mesh, PartitionSpec as P
        from jax import shard_map
        from repro.train.compression import _compress_leaf
        mesh = Mesh(np.array(jax.devices()).reshape(8,), ("data",))
        rng = np.random.default_rng(0)
        gs = rng.normal(size=(8, 1000)).astype(np.float32)

        def local(g, e):
            out, err = _compress_leaf(g[0], e[0], ("data",))
            return out[None], err[None]

        f = shard_map(local, mesh=mesh, in_specs=(P("data"), P("data")),
                      out_specs=(P("data"), P("data")), check_vma=False)
        with mesh:
            out, err = f(jnp.asarray(gs), jnp.zeros_like(jnp.asarray(gs)))
        out = np.asarray(out)
        true_mean = gs.mean(0)
        # every shard holds the same compressed mean
        for s in range(8):
            np.testing.assert_allclose(out[s], out[0], atol=1e-7)
        rel = np.abs(out[0] - true_mean).max() / np.abs(true_mean).max()
        assert rel < 0.05, rel
        # error feedback: err ~= pre-quantization residual, bounded by scale
        assert np.abs(np.asarray(err)).max() <= np.abs(gs).max() / 127.0 * 2
        print("ok", rel)
    """)
    assert "ok" in out
