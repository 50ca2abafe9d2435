"""Multi-device numerical equivalence: the sharded model (8 fake devices,
TP=4 x DP=2, all the shard_map paths active) must match the single-device
model bit-for-bit-ish. Runs in a subprocess so the main pytest process keeps
its single device."""
import os
import subprocess
import sys
import textwrap

import pytest

PROBE = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import SMOKES, MeshConfig, sharding_rules
    from repro.models import build_model, materialize
    from repro.models import layers as ML
    from repro.distributed.sharding import named, param_specs, batch_specs
    from jax.sharding import NamedSharding, PartitionSpec

    failures = []
    for arch in ["llama3.2-3b", "moonshot-v1-16b-a3b", "rwkv6-7b", "starcoder2-7b"]:
        cfg = SMOKES[arch]
        # smoke dims must divide the tiny mesh: d_ff=128/4, heads 4/4, E 4/4
        cfg = dataclasses.replace(cfg, moe_capacity_factor=8.0) if cfg.moe_experts else cfg
        model = build_model(cfg)
        rng = jax.random.PRNGKey(0)
        params = materialize(model.param_infos(), rng)
        B, S = 4, 32
        batch = {
            "tokens": jax.random.randint(rng, (B, S), 0, cfg.vocab),
            "labels": jax.random.randint(rng, (B, S), 0, cfg.vocab),
        }
        loss_ref = float(model.loss(params, batch)[0])

        mesh = jax.make_mesh((2, 4), ("data", "model"))
        mesh_cfg = MeshConfig(data=2, model=4)
        rules = sharding_rules(cfg, mesh_cfg)
        p_sh = named(mesh, param_specs(model, mesh_cfg))
        params_sharded = jax.tree_util.tree_map(
            lambda a, s: jax.device_put(a, s), params, p_sh)
        b_sh = named(mesh, batch_specs(model, mesh_cfg,
                     {k: jax.ShapeDtypeStruct(v.shape, v.dtype) for k, v in batch.items()}))
        batch_sharded = {k: jax.device_put(v, b_sh[k]) for k, v in batch.items()}

        with mesh, ML.activation_sharding(mesh, rules):
            loss_sh = float(jax.jit(lambda p, b: model.loss(p, b)[0])(params_sharded, batch_sharded))
        err = abs(loss_sh - loss_ref) / max(abs(loss_ref), 1e-9)
        print(f"{arch}: ref={loss_ref:.5f} sharded={loss_sh:.5f} rel={err:.2e}")
        if err > 2e-2:
            failures.append(arch)
    assert not failures, failures
    print("SHARDED-EQUIVALENCE OK")
    """
)


@pytest.mark.slow
def test_sharded_loss_matches_single_device():
    env = dict(os.environ)
    env["PYTHONPATH"] = env.get("PYTHONPATH", "") + os.pathsep + os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    # pin the subprocess to the host platform: device-count forcing is
    # CPU-only and probing for a TPU runtime hangs in CI sandboxes
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, "-c", PROBE], capture_output=True, text=True,
                       env=env, timeout=560)
    assert "SHARDED-EQUIVALENCE OK" in r.stdout, r.stdout + "\n" + r.stderr[-3000:]
