"""Multi-device distribution tests.

The main test process sees ONE CpuDevice (the dry-run's 512-device trick
must never leak into tests), so anything needing a real multi-device mesh
runs in a SUBPROCESS with ``XLA_FLAGS=--xla_force_host_platform_device_count=N``.
"""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent


def _run(script: str, devices: int = 4, timeout: int = 480) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = str(REPO / "src")
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(script)],
        capture_output=True, text=True, timeout=timeout, env=env,
    )
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


def test_compressed_dp_matches_uncompressed():
    """int8 + error-feedback cross-pod gradient exchange converges to the
    same place as exact f32 DP on a toy regression (4 fake devices)."""
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.optim.compression import compressed_dp_grads, init_error_feedback

    mesh = jax.make_mesh((4,), ("pod",))
    rng = np.random.default_rng(0)
    w_true = jnp.asarray(rng.normal(size=(8,)), jnp.float32)
    X = jnp.asarray(rng.normal(size=(64, 8)), jnp.float32)
    y = X @ w_true

    def loss_fn(w, batch):
        xb, yb = batch
        return jnp.mean((xb @ w - yb) ** 2)

    from jax.sharding import PartitionSpec as P
    # jit both paths: tracing shard_map/grad 300x dominates runtime otherwise
    grads_fn = jax.jit(compressed_dp_grads(loss_fn, mesh, batch_spec=(P("pod"), P("pod"))))
    exact_grad = jax.jit(jax.grad(loss_fn))

    w_c = jnp.zeros((8,), jnp.float32); err = init_error_feedback(w_c)
    w_e = jnp.zeros((8,), jnp.float32)
    for step in range(300):
        loss_c, g_c, err = grads_fn(w_c, err, (X, y))
        w_c = w_c - 0.05 * g_c
        g_e = exact_grad(w_e, (X, y))
        w_e = w_e - 0.05 * g_e
    final_c = float(loss_fn(w_c, (X, y)))
    final_e = float(loss_fn(w_e, (X, y)))
    print("compressed", final_c, "exact", final_e)
    assert final_c < 1e-3, final_c   # converged despite int8 wire
    assert abs(final_c - final_e) < 1e-3
    """)


def test_moe_ep_all_to_all_matches_single_device():
    """The EP shard_map path (seq-sharded tokens + a2a) must reproduce the
    no-mesh MoE numerics."""
    _run("""
    import dataclasses
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import reduced_config
    from repro.layers.moe import moe_apply, moe_init
    from repro.core.phase_engine import make_pctx

    # capacity high enough that nothing drops: capacity is defined per
    # dispatch group, so drop PATTERNS legitimately differ between the
    # sharded and single-device layouts — only the no-drop regime is
    # bit-comparable.
    cfg = reduced_config("moonshot-v1-16b-a3b", num_experts=4, top_k=2, moe_d_ff=32)
    cfg = dataclasses.replace(cfg, moe_capacity_factor=8.0)
    params = moe_init(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, cfg.d_model), jnp.float32)

    ref, _ = moe_apply(params, x, cfg, make_pctx(None, "prefill"), training=False)

    mesh = jax.make_mesh((2, 2), ("data", "model"))
    pctx = make_pctx(mesh, "prefill")
    # jax.set_mesh is newer-jax; the Mesh context manager is the portable form
    with mesh:
        out, _ = jax.jit(lambda p, xx: moe_apply(p, xx, cfg, pctx, training=False))(params, x)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out), atol=2e-4)
    print("EP matches single-device reference")
    """, devices=4)


def test_train_step_runs_on_small_mesh():
    """One real optimizer step, FSDPxTP-sharded on a 4-device mesh."""
    _run("""
    import jax, jax.numpy as jnp
    from repro.configs import reduced_config
    from repro.launch.mesh import make_host_mesh
    from repro.train.trainer import TrainConfig, init_train_state, jit_train_step

    mesh = make_host_mesh(data=2, model=2)
    cfg = reduced_config("qwen2.5-14b")
    params, opt = init_train_state(cfg, jax.random.PRNGKey(0), mesh, dtype=jnp.float32)
    step = jit_train_step(cfg, TrainConfig(), mesh, jax.eval_shape(lambda: params))
    batch = {
        "tokens": jnp.zeros((4, 32), jnp.int32),
        "targets": jnp.zeros((4, 32), jnp.int32),
        "mask": jnp.ones((4, 32), jnp.float32),
    }
    params, opt, metrics = step(params, opt, batch, jnp.int32(0))
    loss = float(metrics["loss"])
    assert loss == loss and loss > 0  # finite
    print("mesh train step ok, loss", loss)
    """, devices=4)


def test_spatial_disaggregation_split():
    """core.disagg: pod mesh splits into prefill/decode meshes and the KV
    transfer program moves a buffer across."""
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.core.disagg import split_pod_meshes
    from repro.launch.mesh import make_production_mesh  # too big; build small
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    devs = np.array(jax.devices()).reshape(2, 2, 1)
    mesh = Mesh(devs, ("pod", "data", "model"))
    pre, dec = split_pod_meshes(mesh)
    assert pre.devices.size == 2 and dec.devices.size == 2
    kv = jnp.arange(16.0).reshape(4, 4)
    kv_pre = jax.device_put(kv, NamedSharding(pre, P("data", None)))
    kv_dec = jax.device_put(kv_pre, NamedSharding(dec, P("data", None)))
    np.testing.assert_array_equal(np.asarray(kv_dec), np.asarray(kv))
    print("pod split + kv transfer ok")
    """, devices=4)


def test_sharded_decode_matches_unsharded():
    """The full decode_step (batch-leading cache, merge path, scatter) on a
    (data=2, model=2) mesh must agree with the single-device program."""
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import reduced_config
    from repro.core.phase_engine import PhaseEngine
    from repro.models import get_model

    cfg = reduced_config("deepseek-7b")
    api = get_model(cfg)
    params = api.init(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    b, prompt, max_len = 4, 8, 32
    tokens = jnp.asarray(np.random.default_rng(0).integers(0, cfg.vocab_size, (b, prompt)), jnp.int32)

    def roll(mesh):
        eng = PhaseEngine(cfg, mesh, max_len=max_len)
        pa = jax.eval_shape(lambda: params)
        logits, kv = eng.prefill_program(pa, b, prompt).fn(params, tokens)
        cache = eng.relayout_program(b, prompt, max_len).fn(kv)
        dec = eng.decode_program(pa, b, max_len)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        outs = [tok]
        lengths = jnp.full((b,), prompt, jnp.int32)
        for i in range(3):
            lg, cache = dec.fn(params, tok, cache, lengths + i)
            tok = jnp.argmax(lg, -1).astype(jnp.int32)
            outs.append(tok)
        return np.stack([np.asarray(t) for t in outs])

    ref = roll(None)
    from repro.launch.mesh import make_host_mesh
    mesh = make_host_mesh(data=2, model=2)
    out = roll(mesh)
    np.testing.assert_array_equal(ref, out)
    print("sharded decode == unsharded decode")
    """, devices=4)
