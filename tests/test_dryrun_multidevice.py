"""Multi-device sharding machinery tests (subprocess: 16 fake host devices,
scaled-down mesh (2, 4, 2) exercising the same code paths as production;
keeps the main test process at 1 device per the assignment note)."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
    import json
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs import get_arch
    from repro.configs.base import ShapeConfig
    from repro.models.registry import build_model, make_inputs
    from repro.models.sharding import MeshCtx
    from repro.train.steps import (batch_shardings, make_train_step,
                                   training_state_specs)
    from repro.train.optimizer import adamw_init

    mesh = jax.make_mesh((2, 4, 2), ("pod", "data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 3)
    ctx = MeshCtx(mesh)
    cfg = get_arch("{arch}").reduced()
    model = build_model(cfg, max_pos=32)
    shape = ShapeConfig("t", 32, 8, "train")
    params = model.init_params(jax.random.PRNGKey(0))
    opt = adamw_init(params)
    step = make_train_step(model, ctx)
    pstore, ospecs = training_state_specs(model, ctx)
    bshard = batch_shardings(cfg, shape, ctx)
    jitted = jax.jit(step, in_shardings=(pstore, ospecs, bshard),
                     out_shardings=(pstore, ospecs, ctx.replicated()))
    batch = make_inputs(cfg, shape, seed=1)
    for k in ("tokens", "labels"):
        if k in batch:
            batch[k] = batch[k] % cfg.vocab
    # run distributed AND single-device; losses must agree
    p2, o2, loss_dist = jitted(params, opt, batch)
    from repro.train.steps import make_train_step as mts
    step1 = jax.jit(mts(model, None))
    p1, o1, loss_1dev = step1(params, opt, batch)
    print(json.dumps({{
        "loss_dist": float(loss_dist),
        "loss_1dev": float(loss_1dev),
        "params_close": bool(all(
            np.allclose(np.asarray(a, np.float32), np.asarray(b, np.float32),
                        rtol=3e-2, atol=3e-2)
            for a, b in zip(jax.tree.leaves(p2), jax.tree.leaves(p1)))),
    }}))
    """
)


@pytest.mark.parametrize("arch", ["qwen2_0_5b", "olmoe_1b_7b", "mamba2_2_7b"])
def test_distributed_train_step_matches_single_device(arch):
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    out = subprocess.run(
        [sys.executable, "-c", _SCRIPT.format(arch=arch)],
        capture_output=True, text=True, env=env, cwd=os.path.dirname(os.path.dirname(__file__)),
        timeout=420,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert abs(res["loss_dist"] - res["loss_1dev"]) < 0.05, res
    assert res["params_close"], res
