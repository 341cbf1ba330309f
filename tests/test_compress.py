"""int8 error-feedback gradient compression: wire dtype + convergence."""

import pytest

from subproc import run_python

# slow lane: jax/pallas compile-heavy; skipped by `make test-fast` / CI per-push
pytestmark = pytest.mark.slow
import textwrap

CODE = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp, numpy as np
    from repro.launch.mesh import make_mesh
    from repro.train import compress

    mesh = make_mesh((4,), ("pod",))

    # --- quadratic regression: compressed DP matches exact DP -------------
    rng = np.random.default_rng(0)
    X = rng.normal(size=(64, 16)).astype(np.float32)
    w_true = rng.normal(size=(16,)).astype(np.float32)
    y = X @ w_true

    def loss_fn(params, batch):
        pred = batch["tokens"] @ params["w"]
        return jnp.mean((pred - batch["labels"]) ** 2)

    grad_fn = jax.jit(compress.make_compressed_grad_fn(loss_fn, mesh))
    batch = {"tokens": jnp.asarray(X), "labels": jnp.asarray(y)}

    params = {"w": jnp.zeros(16)}
    err = compress.init_error_state(params)
    params_ref = {"w": jnp.zeros(16)}
    for i in range(300):
        loss, grads, err = grad_fn(params, batch, err)
        params = jax.tree.map(lambda p, g: p - 0.05 * g, params, grads)
        _, g_ref = jax.value_and_grad(loss_fn)(params_ref, batch)
        params_ref = jax.tree.map(lambda p, g: p - 0.05 * g, params_ref,
                                  g_ref)
    err_c = float(jnp.linalg.norm(params["w"] - w_true))
    err_e = float(jnp.linalg.norm(params_ref["w"] - w_true))
    assert err_c < err_e + 0.05, (err_c, err_e)   # converged comparably

    # --- the wire really is int8 ------------------------------------------
    hlo = grad_fn.lower(params, batch, err).compile().as_text()
    assert any("s8[" in l and "all-gather" in l for l in hlo.splitlines()),\\
        "no int8 all-gather on the wire"
    f32_ag = [l for l in hlo.splitlines()
              if "all-gather" in l and "f32[4,64" in l]
    assert not f32_ag, "full-width gradient all-gather still present"
    print("COMPRESS_OK", round(err_c, 4), round(err_e, 4))
""")


def test_compressed_allreduce():
    out = run_python(["-c", CODE])
    assert "COMPRESS_OK" in out.stdout, (out.stdout[-1000:],
                                         out.stderr[-2500:])
