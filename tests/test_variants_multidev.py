"""The §Perf variant paths must be *correct*, not just compilable: under a
real 8-device mesh, the dp layout and the tp layout must produce the same
loss as the single-device model; int8-KV decode must match bf16-KV decode to
quantization tolerance. Subprocess keeps the main process single-device."""
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
    from repro.distributed.sharding import named, param_specs, batch_specs, cache_specs
    from repro.models.params import abstract

    mesh = jax.make_mesh((2, 4), ("data", "model"))
    mesh_cfg = MeshConfig(data=2, model=4)
    rng = jax.random.PRNGKey(0)

    # --- dp vs tp layout: identical loss ---------------------------------
    base = SMOKES["tinyllama-1.1b"]
    model = build_model(base)
    params = materialize(model.param_infos(), rng)
    B, S = 4, 32
    batch = {"tokens": jax.random.randint(rng, (B, S), 0, base.vocab),
             "labels": jax.random.randint(rng, (B, S), 0, base.vocab)}
    ref = float(model.loss(params, batch)[0])

    for layout in ("tp", "dp"):
        cfg = dataclasses.replace(base, layout=layout)
        m = build_model(cfg)
        rules = sharding_rules(cfg, mesh_cfg)
        p_sh = named(mesh, param_specs(m, mesh_cfg))
        ps = jax.tree_util.tree_map(lambda a, s: jax.device_put(a, s), params, p_sh)
        with mesh, ML.activation_sharding(mesh, rules):
            got = float(jax.jit(lambda p, b: m.loss(p, b)[0])(ps, batch))
        err = abs(got - ref) / abs(ref)
        print(f"layout={layout}: loss={got:.5f} ref={ref:.5f} rel={err:.2e}")
        assert err < 2e-2, layout

    # --- int8 KV decode on the mesh vs bf16 KV ----------------------------
    cfgq = dataclasses.replace(base, kv_cache_dtype="int8")
    mq = build_model(cfgq)
    tokens = jax.random.randint(rng, (B, 17), 0, base.vocab)
    outs = {}
    for name, m in (("bf16", model), ("int8", mq)):
        cfg_m = m.cfg
        rules = sharding_rules(cfg_m, mesh_cfg)
        p_sh = named(mesh, param_specs(m, mesh_cfg))
        ps = jax.tree_util.tree_map(lambda a, s: jax.device_put(a, s), params, p_sh)
        with mesh, ML.activation_sharding(mesh, rules):
            cache = materialize(m.cache_infos(B, 24), rng)
            c_sh = named(mesh, cache_specs(m, mesh_cfg, B, 24))
            cache = jax.tree_util.tree_map(lambda a, s: jax.device_put(a, s), cache, c_sh)
            def run(p, c, t):
                _, c = m.prefill(p, {"tokens": t[:, :16]}, c)
                lg, _ = m.decode_step(p, c, t[:, 16:17])
                return lg
            outs[name] = np.asarray(jax.jit(run)(ps, cache, tokens), np.float32)
    rel = np.abs(outs["int8"] - outs["bf16"]).max() / (np.abs(outs["bf16"]).max() + 1e-9)
    print(f"int8-vs-bf16 KV decode rel err: {rel:.3e}")
    # smoke heads are 16-dim, so per-token int8 scales are coarse; the
    # full-config 128-dim heads land near 1e-2 (see test_models notes).
    # This bound checks the quantized path runs correctly on the mesh.
    assert rel < 0.2
    # argmax token agreement is the serving-level criterion
    agree = (outs["int8"].argmax(-1) == outs["bf16"].argmax(-1)).mean()
    print(f"argmax agreement: {agree:.2f}")
    print("VARIANTS OK")
    """
)


@pytest.mark.slow
def test_perf_variants_numerically_correct_on_mesh():
    env = dict(os.environ)
    env["PYTHONPATH"] = env.get("PYTHONPATH", "") + os.pathsep + os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    # pin the subprocess to the host platform: device-count forcing is
    # CPU-only and probing for a TPU runtime hangs in CI sandboxes
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, "-c", PROBE], capture_output=True, text=True,
                       env=env, timeout=560)
    assert "VARIANTS OK" in r.stdout, r.stdout + "\n" + r.stderr[-3000:]
