"""GPipe pipeline over the 'pod' axis == sequential stack (4 fake devices)."""

import json
import os
import subprocess
import sys
import textwrap

PROG = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json
    import jax
    from repro.launch.mesh import make_mesh
    import jax.numpy as jnp
    import numpy as np
    from repro.distributed.pipeline import pipeline_apply

    mesh = make_mesh((4,), ("pod",))
    n_stage, b, d = 4, 8, 16
    key = jax.random.PRNGKey(0)
    ws = jax.random.normal(key, (n_stage, d, d)) / jnp.sqrt(d)
    x = jax.random.normal(jax.random.fold_in(key, 1), (b, d))

    def stage_fn(w, xb):
        return xb + jnp.tanh(xb @ w)      # residual stage

    y_pipe = pipeline_apply(stage_fn, ws, x, mesh, axis="pod", n_micro=4)

    y_seq = x
    for i in range(n_stage):
        y_seq = stage_fn(ws[i], y_seq)

    rel = float(jnp.linalg.norm(y_pipe - y_seq) / jnp.linalg.norm(y_seq))
    # gradients flow through the pipeline too
    def loss(ws):
        return jnp.sum(pipeline_apply(stage_fn, ws, x, mesh,
                                      axis="pod", n_micro=2) ** 2)
    g = jax.grad(loss)(ws)
    gfinite = bool(jnp.all(jnp.isfinite(g)))
    print(json.dumps({"rel": rel, "grad_finite": gfinite,
                      "grad_norm": float(jnp.linalg.norm(g))}))
""")


def test_pipeline_matches_sequential():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", PROG], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["rel"] < 1e-5, res
    assert res["grad_finite"] and res["grad_norm"] > 0
