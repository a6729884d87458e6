"""GPipe pipeline over a forced multi-device host mesh (subprocess)."""

import os
import subprocess
import sys
import textwrap

import pytest

from repro.distributed.pipeline import bubble_fraction

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import sys
    sys.path.insert(0, "src")
    import jax, jax.numpy as jnp, numpy as np
    from repro.distributed.pipeline import pipeline_forward
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((4,), ("pod",))
    S, D, B = 4, 8, 8
    ks = jax.random.split(jax.random.PRNGKey(0), S)
    params = jnp.stack([jax.random.normal(k, (D, D)) / np.sqrt(D) for k in ks])
    x = jax.random.normal(jax.random.PRNGKey(9), (B, D))

    def stage_fn(w, h):
        return jnp.tanh(h @ w)

    y = pipeline_forward(stage_fn, params, x, mesh=mesh, n_micro=4)
    # reference: sequential application of all stages
    ref = x
    for s in range(S):
        ref = stage_fn(params[s], ref)
    err = float(jnp.abs(y - ref).max())
    assert err < 1e-5, f"pipeline mismatch {err}"

    # gradients flow through the pipeline (training viability)
    def loss(params):
        return jnp.sum(pipeline_forward(stage_fn, params, x, mesh=mesh,
                                        n_micro=4) ** 2)
    g = jax.grad(loss)(params)
    assert bool(jnp.isfinite(g).all()) and float(jnp.abs(g).sum()) > 0
    print("PIPELINE_OK", err)
""")


def test_pipeline_matches_sequential_and_differentiates():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                       text=True, env=env, cwd=os.path.dirname(
                           os.path.dirname(os.path.abspath(__file__))),
                       timeout=300)
    assert "PIPELINE_OK" in r.stdout, r.stdout + r.stderr


def test_bubble_fraction():
    assert bubble_fraction(4, 4) == pytest.approx(3 / 7)
    assert bubble_fraction(2, 16) == pytest.approx(1 / 17)
    assert bubble_fraction(1, 8) == 0.0
