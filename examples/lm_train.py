"""End-to-end LM training driver (deliverable b): train a small decoder for
a few hundred steps on the synthetic pipeline, with checkpoints + resume.

Default is a ~19M-param model x 200 steps (CPU-friendly). ``--big`` switches
to a ~110M-param model (same code path; slower on this container). On TPU
the identical driver runs the full assigned configs under the production
mesh (see repro.launch.train).

Run:  PYTHONPATH=src python examples/lm_train.py [--steps 200] [--big]
"""

import argparse
import dataclasses

import jax

from repro.nn.lm.config import ModelConfig
from repro.nn.lm import model as model_lib
from repro.train import data_pipeline, optimizer as opt_lib, steps
from repro.train.loop import train_loop

SMALL = ModelConfig(
    name="repro-19m", family="dense", n_layers=4, d_model=256, n_heads=4,
    n_kv_heads=2, head_dim=64, d_ff=1024, vocab_size=32000, act="silu",
    qk_norm=True, dtype="float32", tie_embeddings=True)

BIG = dataclasses.replace(SMALL, name="repro-110m", n_layers=8, d_model=640,
                          n_heads=10, n_kv_heads=2, d_ff=2560)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--big", action="store_true")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_lm_ckpt")
    args = ap.parse_args()

    cfg = BIG if args.big else SMALL
    ocfg = opt_lib.OptConfig(lr=3e-3, warmup_steps=20,
                             total_steps=args.steps)
    params = model_lib.init_model(jax.random.PRNGKey(0), cfg)
    n = sum(l.size for l in jax.tree_util.tree_leaves(params))
    print(f"model={cfg.name} params={n / 1e6:.1f}M "
          f"tokens/step={args.batch * args.seq}")
    state = opt_lib.init_state(params, ocfg)
    step = jax.jit(steps.make_train_step(cfg, ocfg), donate_argnums=(0,))
    batches = data_pipeline.synthetic_batches(cfg, args.batch, args.seq)
    out = train_loop(state, step, batches, num_steps=args.steps,
                     ckpt_dir=args.ckpt_dir, ckpt_every=100, log_every=20)
    hist = out["history"]
    print(f"loss: {hist[0][1]:.3f} -> {hist[-1][1]:.3f} "
          f"({'improved' if hist[-1][1] < hist[0][1] else 'NO IMPROVEMENT'})")


if __name__ == "__main__":
    main()
