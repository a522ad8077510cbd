"""Distribution-layer lowering tests under a forced multi-device CPU.

Run in subprocesses because XLA device count locks at first jax init.
Covers: compressed-DP train step (EF-int8 over 'pod'), GPipe pipeline
loss over 'pod', and a miniature dryrun cell on a (2,2,2) mesh.
"""
import os
import subprocess
import sys
import textwrap

import pytest

# the distribution layer is not part of this tree yet; these lowering
# tests resume automatically once a PR adds repro.dist
pytest.importorskip("repro.dist", reason="repro.dist not in tree")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV = dict(os.environ,
           XLA_FLAGS="--xla_force_host_platform_device_count=8",
           PYTHONPATH="src",
           JAX_PLATFORMS="cpu")


def _run(code: str):
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=ENV,
                       cwd=REPO, timeout=900)
    assert r.returncode == 0, r.stdout + r.stderr
    return r.stdout


@pytest.mark.slow
def test_compressed_pod_train_step_lowers():
    out = _run("""
        import jax, jax.numpy as jnp
        mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
        from repro.configs import get_smoke_config
        from repro.models.model import build, dummy_batch
        from repro.train.train_step import TrainConfig, init_state
        from repro.dist.compress import (init_error_state,
                                         make_compressed_train_step)
        cfg = get_smoke_config("granite-3-2b")
        m = build(cfg)
        params = m.init(jax.random.PRNGKey(0))
        state = init_state(params)
        err = init_error_state(params)
        step = make_compressed_train_step(m, TrainConfig(), mesh)
        batch = dummy_batch(cfg, 8, 32)
        with mesh:
            lowered = jax.jit(step).lower(state, err, batch)
            compiled = lowered.compile()
            txt = compiled.as_text()
        assert "all-gather" in txt or "all-reduce" in txt
        # int8 payload crosses pods (the compressed wire format)
        assert "s8[" in txt, "expected int8 collective payload"
        state2, err2, metrics = compiled(state, err, batch)
        assert bool(jnp.isfinite(metrics["loss"]))
        print("OK compressed step")
    """)
    assert "OK compressed step" in out


@pytest.mark.slow
def test_pp_loss_lowers_and_differentiates():
    out = _run("""
        import jax, jax.numpy as jnp
        mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
        from repro.configs import get_smoke_config
        from repro.models.model import build
        from repro.dist.pp import make_pp_loss
        import dataclasses
        # fp32 params: XLA CPU 0.8.x CHECK-crashes in AllReducePromotion on
        # bf16 all-reduces inside manual-axis while loops (TPU unaffected)
        cfg = dataclasses.replace(get_smoke_config("granite-3-2b"),
                                  dtype="float32")  # 2 layers = 2 stages
        m = build(cfg)
        params = m.init(jax.random.PRNGKey(0))
        loss_fn = make_pp_loss(cfg, mesh, n_micro=4)
        toks = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0,
                                  cfg.vocab_size, dtype=jnp.int32)
        with mesh:
            val_grad = jax.jit(jax.value_and_grad(loss_fn))
            loss, grads = val_grad(params, toks)
        assert bool(jnp.isfinite(loss)), loss
        gn = sum(float(jnp.sum(jnp.abs(g))) for g in jax.tree.leaves(grads))
        assert gn > 0
        print("OK pp loss", float(loss))
    """)
    assert "OK pp loss" in out


@pytest.mark.slow
def test_mini_dryrun_decode_cell():
    out = _run("""
        import jax, jax.numpy as jnp
        mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
        from repro.configs import get_smoke_config
        from repro.configs.base import ShapeConfig
        from repro.dist import sharding as shd
        from repro.launch import specs as S
        from repro.models.model import build
        cfg = get_smoke_config("yi-6b")
        sc = ShapeConfig("d", 64, 16, "decode")
        model = build(cfg, constrain=shd.make_constrain(mesh))
        pspecs = S.param_specs(model, cfg, mesh)
        specs = S.input_specs(model, cfg, sc, mesh)
        def fn(params, cache, tokens, pos):
            return model.decode_step(params, cache, tokens, pos)
        with mesh:
            compiled = jax.jit(fn, donate_argnums=(1,)).lower(
                pspecs, specs["cache"], specs["tokens"], specs["pos"]
            ).compile()
        assert compiled.cost_analysis()["flops"] > 0
        print("OK mini dryrun")
    """)
    assert "OK mini dryrun" in out
