"""End-to-end training driver with EC-coded quorum checkpointing.

CPU-scale by default (reduced config) so it is runnable here:

  PYTHONPATH=src python -m repro.launch.train --arch gemma3_1b --steps 50 \
      --ckpt-every 20 [--crash-at 30] [--compress-grads] [--full]

``--full`` uses the full architecture config (for real clusters).
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import get_arch
from repro.configs.base import ShapeConfig
from repro.models.registry import build_model
from repro.train.checkpoint import ECCheckpointStore
from repro.train.data import DataConfig, SyntheticLM
from repro.train.optimizer import AdamWConfig, adamw_init
from repro.train.steps import make_train_step


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma3_1b")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--ckpt-hosts", type=int, default=8)
    ap.add_argument("--ckpt-parity", type=int, default=2)
    ap.add_argument("--crash-at", type=int, default=0,
                    help="simulate trainer crash+restore at this step")
    ap.add_argument("--kill-hosts", type=int, default=0,
                    help="crash this many checkpoint hosts before restore")
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--lr", type=float, default=1e-3)
    args = ap.parse_args(argv)

    cfg = get_arch(args.arch)
    if not args.full:
        cfg = cfg.reduced()
    model = build_model(cfg, max_pos=args.seq)
    shape = ShapeConfig("drv", args.seq, args.batch, "train")
    data = SyntheticLM(DataConfig(vocab=cfg.vocab, seq_len=args.seq,
                                  global_batch=args.batch))
    params = model.init_params(jax.random.PRNGKey(0))
    opt_state = adamw_init(params)
    opt_cfg = AdamWConfig(lr=args.lr)
    if args.compress_grads:
        # error-feedback int8 gradient compression around the DP reduction
        # (here: demonstrated on the single-host loop; at scale the compress
        # wraps the cross-pod all-reduce — see train/compress.py).
        from repro.train import compress as gc_mod
        from repro.train.optimizer import adamw_update as _upd

        def step_raw(params, opt_state, residuals, batch):
            loss, grads = jax.value_and_grad(
                lambda p: model.loss_fn(p, batch))(params)
            qs, scales, residuals = gc_mod.compress_tree(grads, residuals)
            grads = gc_mod.decompress_tree(qs, scales, grads)
            params, opt_state = _upd(params, grads, opt_state, opt_cfg)
            return params, opt_state, residuals, loss

        residuals = None
        _jit = jax.jit(step_raw)

        def step_fn(params, opt_state, batch):
            nonlocal residuals
            if residuals is None:
                _, g0 = jax.value_and_grad(lambda p: model.loss_fn(p, batch))(params)
                residuals = gc_mod.init_residuals(g0)
            params, opt_state, residuals, loss = _jit(params, opt_state,
                                                      residuals, batch)
            return params, opt_state, loss
    else:
        step_fn = jax.jit(make_train_step(model, None, opt_cfg))
    store = ECCheckpointStore(n_hosts=args.ckpt_hosts, parity=args.ckpt_parity)
    print(f"[train] {cfg.name} reduced={not args.full} params="
          f"{model.n_params()/1e6:.1f}M fault_budget={store.fault_budget()} hosts")

    losses = []
    ckpt_stats = []
    step = 0
    t0 = time.time()
    while step < args.steps:
        batch = {k: jnp.asarray(v) for k, v in data.next_batch().items()}
        params, opt_state, loss = step_fn(params, opt_state, batch)
        losses.append(float(loss))
        step += 1
        if args.ckpt_every and step % args.ckpt_every == 0:
            st = store.save(step, {"params": params, "opt": opt_state,
                                   "data": data.state()})
            ckpt_stats.append(st)
            print(f"[ckpt] step={step} {st.bytes_written/1e6:.2f} MB in "
                  f"{st.virtual_seconds*1e3:.1f} virtual-ms, "
                  f"{st.blocks_written}/{st.blocks_total} blocks rewritten")
        if args.crash_at and step == args.crash_at:
            print(f"[crash] trainer dies at step {step}; "
                  f"{args.kill_hosts} checkpoint hosts die too")
            if args.kill_hosts:
                store.crash_hosts([f"s{i}" for i in range(args.kill_hosts)])
            restored = store.restore()
            assert restored is not None, "restore failed"
            rstep, st2 = restored
            params = jax.tree.map(jnp.asarray, st2["params"])
            opt_state = jax.tree.map(jnp.asarray, st2["opt"])
            opt_state["step"] = jnp.asarray(opt_state["step"])
            data.restore(st2["data"])
            print(f"[restore] resumed from step {rstep} (k-of-n decode OK)")
            step = rstep
            args.crash_at = 0  # once
    dt = time.time() - t0
    print(f"[done] {args.steps} steps in {dt:.1f}s wall; "
          f"loss {losses[0]:.3f} -> {losses[-1]:.3f}")
    return {"losses": losses, "ckpts": ckpt_stats}


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
