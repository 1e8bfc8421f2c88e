"""End-to-end training driver (assignment deliverable b): a ~15M-param
transformer trained a few hundred steps on learnable Markov-chain data,
with checkpointing, an injected node failure, automatic restore, and
bit-identical data replay — the full fault-tolerance path on CPU.

Run:  PYTHONPATH=src python examples/train_lm.py          (~5-10 min CPU)
      PYTHONPATH=src python examples/train_lm.py --steps 100   (faster)
"""
import argparse
import dataclasses
import tempfile
import time

import jax
import jax.numpy as jnp

from repro.checkpoint import CheckpointManager
from repro.compile_cache import use_compile_cache
from repro.configs.registry import get_config
from repro.data import BatchIterator, MarkovLMDataset
from repro.distrib import sharding as shlib
from repro.ft import Supervisor
from repro.launch.mesh import make_mesh
from repro.launch.steps import jit_train_step
from repro.models.config import ModelConfig
from repro.optim import AdamWConfig, adamw_init


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--global-batch", type=int, default=16)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--fail-at", type=int, default=120)
    args = ap.parse_args()
    use_compile_cache()

    # ~15M params: a shrunken qwen2.5 (same family/topology).
    cfg = dataclasses.replace(
        get_config("qwen2.5-3b"),
        n_layers=4, d_model=256, n_heads=8, n_kv_heads=2, head_dim=32,
        d_ff=1024, vocab=512, dtype="float32", remat="none",
    )
    print(f"model: {cfg.n_params()/1e6:.1f}M params")

    mesh = make_mesh((1, 1), ("data", "model"))
    shlib.set_rules(mesh)
    dataset = MarkovLMDataset(vocab=cfg.vocab, seq_len=args.seq_len,
                              branching=4)
    print(f"data: order-1 Markov chain, entropy rate "
          f"{dataset.entropy_rate:.3f} nats/token")

    opt_cfg = AdamWConfig(lr_peak=8e-3, warmup_steps=20,
                          total_steps=args.steps)
    batch_abs = {
        k: jax.ShapeDtypeStruct((args.global_batch, args.seq_len), jnp.int32)
        for k in ("tokens", "labels")
    }
    with shlib.rules_context(mesh):
        step_fn, (p_sh, o_sh, b_sh) = jit_train_step(
            cfg, mesh, batch_abs, opt_cfg=opt_cfg
        )
        from repro.configs.registry import get_model

        api = get_model(cfg)
        params = api.init_params(cfg, jax.random.PRNGKey(0))
        opt = adamw_init(params)

        ckpt_dir = tempfile.mkdtemp(prefix="repro_train_lm_")
        ckpt = CheckpointManager(ckpt_dir, keep=2)
        sup = Supervisor(ckpt, ckpt_every=50)
        losses = []

        def one_step(state, step):
            it = BatchIterator(dataset, args.global_batch, host_index=0,
                               host_count=1, start_step=step)
            params, opt, metrics = step_fn(
                state["params"], state["opt"], it.next_local()
            )
            loss = float(metrics["loss"])
            losses.append((step, loss))
            if step % 25 == 0:
                print(f"  step {step:4d}  loss {loss:.4f}", flush=True)
            return {"params": params, "opt": opt}

        def restore(state, step):
            if step is None:
                return state, 0
            restored, got = ckpt.restore(state, step)
            print(f"  >> restored checkpoint at step {got}")
            return restored, got

        t0 = time.time()
        state, report = sup.run(
            {"params": params, "opt": opt}, one_step, args.steps,
            failure_at=args.fail_at, restore_fn=restore,
        )
        dt = time.time() - t0

    first = losses[0][1]
    final = losses[-1][1]
    print(
        f"\ntrained {args.steps} steps in {dt:.0f}s "
        f"({args.steps*args.global_batch*args.seq_len/dt:.0f} tok/s): "
        f"loss {first:.3f} → {final:.3f} "
        f"(entropy rate {dataset.entropy_rate:.3f}); "
        f"injected failures recovered: {report['restarts']}"
    )
    assert final < first - 0.5, "loss should drop by >0.5 nats"
    assert report["restarts"] == 1, "expected exactly one injected failure"
    print("train_lm OK")


if __name__ == "__main__":
    main()
