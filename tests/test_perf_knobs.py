"""§Perf knob paths (tuned_hints, rs_epilogue) must be semantics-preserving:
same loss/gradients as the baseline path, only placement/precision of the
TP epilogue boundary changes (bf16 reduce-scatter, documented)."""

import pytest

from subproc import run_python

# slow lane: jax/pallas compile-heavy; skipped by `make test-fast` / CI per-push
pytestmark = pytest.mark.slow
import textwrap

CODE = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import smoke_config
    from repro.launch.mesh import make_mesh
    from repro.sharding import default_rules
    from repro.train import optim, step as step_mod

    base = smoke_config("starcoder2-7b")
    base = dataclasses.replace(
        base, num_layers=2, d_model=64, d_ff=128, num_heads=8,
        num_kv_heads=4, head_dim=16, vocab_size=128, remat=False)
    mesh = make_mesh((2, 4), ("data", "model"))
    rules = default_rules()
    opt = optim.OptConfig(warmup_steps=0)
    key = jax.random.key(0)
    toks = jax.random.randint(key, (8, 33), 0, 128, jnp.int32)
    batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}

    losses = {}
    grads0 = {}
    for name, kw in [("base", {}),
                     ("tuned", {"tuned_hints": True}),
                     ("rs", {"rs_epilogue": True}),
                     ("both", {"tuned_hints": True, "rs_epilogue": True})]:
        cfg = dataclasses.replace(base, **kw)
        state, _ = step_mod.init_state(cfg, opt, key)
        fn = jax.jit(step_mod.make_train_step(cfg, mesh, rules, opt))
        new_state, mets = fn(state, batch)
        losses[name] = float(mets["loss"])
        grads0[name] = np.asarray(
            jax.tree.leaves(new_state["params"])[0]).ravel()[:8]

    print("losses:", {k: round(v, 5) for k, v in losses.items()})
    for name in ("tuned", "rs", "both"):
        assert abs(losses[name] - losses["base"]) < 2e-3, (name, losses)
        np.testing.assert_allclose(grads0[name], grads0["base"],
                                   rtol=5e-2, atol=5e-3)
    print("PERF_KNOBS_OK")
""")


def test_perf_knobs_preserve_semantics():
    out = run_python(["-c", CODE])
    assert "PERF_KNOBS_OK" in out.stdout, (out.stdout[-1500:],
                                           out.stderr[-3000:])
