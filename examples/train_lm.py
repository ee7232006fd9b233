"""End-to-end driver: train the ~135M smollm architecture for a few hundred
steps with the full production stack — sharded train step, async
checkpointing, fault-tolerant restart loop, straggler-tolerant loader.

    PYTHONPATH=src python examples/train_lm.py --steps 200 --layers 6

(--layers reduces depth for CPU wall time; pass 30 for the full config.)
"""
import argparse
import dataclasses

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.data.pipeline import PrefetchLoader, SyntheticLM
from repro.env import enable_compile_cache
from repro.models import transformer
from repro.optim.optimizer import AdamW, cosine_schedule
from repro.train.train_step import make_train_step
from repro.train.trainer import Trainer, TrainerConfig, run_with_restarts


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--layers", type=int, default=6)
    ap.add_argument("--d-model", type=int, default=256)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_lm")
    ap.add_argument("--fail-at", type=int, default=-1,
                    help="inject a node failure at this step (demo)")
    args = ap.parse_args()

    cfg = get_config("smollm-135m")
    cfg = dataclasses.replace(
        cfg, n_layers=args.layers, d_model=args.d_model,
        d_ff=args.d_model * 8 // 3 // 64 * 64 or 256,
        n_heads=4, n_kv_heads=2, head_dim=args.d_model // 4,
        vocab=2048).resolve_for_mesh(tp=1)
    print(f"model: {cfg.param_count()/1e6:.1f}M params "
          f"({cfg.n_layers}L d={cfg.d_model})")

    opt = AdamW(lr=cosine_schedule(3e-3, 20, args.steps), weight_decay=0.01,
                clip_norm=1.0)
    step = make_train_step(cfg, opt, unroll=False)   # scanned layers

    # ONE injector across restarts — a node dies once, not on every retry
    from repro.train.trainer import FailureInjector
    failer = FailureInjector(args.fail_at) if args.fail_at >= 0 else None

    def make_trainer():
        loader = PrefetchLoader(SyntheticLM(cfg.vocab, args.seq, seed=0),
                                batch=args.batch, seed=0)

        def init_state():
            params = transformer.init_params(jax.random.PRNGKey(0), cfg)
            return params, opt.init(params), ()

        return Trainer(cfg, step, init_state, loader, args.ckpt_dir,
                       TrainerConfig(total_steps=args.steps, ckpt_every=20,
                                     log_every=20),
                       failer=failer)

    out = run_with_restarts(make_trainer, max_failures=2)
    print(f"done: steps={out['steps']} final_loss={out['final_loss']:.4f} "
          f"restarts={out['restarts']} wall={out['wall_s']:.1f}s "
          f"straggler_misses={out['straggler_misses']}")
    for h in make_trainer().history:
        pass
    print("loss curve:", [round(l, 3) for l in out["losses"][::20]])


if __name__ == "__main__":
    main()
