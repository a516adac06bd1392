"""Train a ~100M-param LM for a few hundred steps with the full stack:
fault-tolerant loop, async checkpointing, optional fault drill.

    PYTHONPATH=src python examples/train_lm.py --steps 200
"""

import argparse
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.data import lm_batch
from repro.distributed.fault_tolerance import FaultInjector
from repro.launch.compile_cache import enable_compile_cache
from repro.models import transformer as tx
from repro.training.optimizer import adamw
from repro.training.train_loop import TrainLoopConfig, make_train_step, run


def build_config(vocab: int = 8192) -> tx.TransformerConfig:
    """~100M params: 12 layers, d=768, llama-style."""
    return tx.TransformerConfig(
        name="lm-100m", n_layers=12, d_model=768, n_heads=12, n_kv_heads=4,
        head_dim=64, d_ff=2048, vocab=vocab, tie_embeddings=True,
        remat=False, attn_chunk_q=128, attn_chunk_kv=128, xent_chunk=64,
        dtype=jnp.float32)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_lm_ckpt")
    ap.add_argument("--inject-fault-at", type=int, default=None)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = build_config()
    params = tx.init_params(cfg, jax.random.PRNGKey(0))
    n = sum(x.size for x in jax.tree_util.tree_leaves(params))
    print(f"model: {cfg.name}, {n / 1e6:.1f}M params")

    opt = adamw(lr=3e-4, weight_decay=0.01)
    state = opt.init(params)
    step = jax.jit(make_train_step(
        lambda p, b: tx.loss_fn(cfg, p, b), opt))

    def batches(i):
        b = lm_batch(args.batch, args.seq, cfg.vocab, seed=i)
        return {k: jnp.asarray(v) for k, v in b.items()}

    injector = FaultInjector(fail_at_steps=(args.inject_fault_at,)) \
        if args.inject_fault_at else None
    losses = []
    res = run(step, params, state, batches,
              TrainLoopConfig(total_steps=args.steps, checkpoint_every=50,
                              checkpoint_dir=args.ckpt_dir, log_every=20),
              injector=injector,
              on_step=lambda s, l: (losses.append(l),
                                    print(f"step {s:4d} loss {l:.4f}")
                                    if s % 20 == 0 else None))
    first = np.mean(res.losses[:10])
    last = np.mean(res.losses[-10:])
    print(f"\ndone: {res.final_step} steps, loss {first:.3f} → {last:.3f}, "
          f"restarts={res.restarts}, stragglers={len(res.straggler_steps)}")
    assert last < first, "loss did not improve"


if __name__ == "__main__":
    main()
