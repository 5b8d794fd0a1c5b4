"""Example: fault-tolerant LM training with the repro stack.

Default runs a pocket-sized config for CPU; ``--arch mamba2-130m --full``
trains the real ~129M-parameter Mamba2 for a few hundred steps (the
assignment's 100M-scale end-to-end driver — budget hours on CPU, minutes on
a TPU host).

    PYTHONPATH=src python examples/train_lm.py [--steps 60] [--full]
"""

import argparse

import jax

from repro.checkpoint.store import CheckpointManager
from repro.compile_cache import enable_compilation_cache
from repro.data.tokens import TokenStream
from repro.models.config import get_config
from repro.models.model import Model
from repro.train.loop import FailureInjector, run_training
from repro.train.step import TrainConfig, init_train_state, train_step


def main():
    enable_compilation_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mamba2-130m")
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--full", action="store_true",
                    help="full config (~100M params) instead of the smoke one")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_ckpt")
    ap.add_argument("--inject-failure", type=int, default=-1,
                    help="step at which to simulate a node failure")
    args = ap.parse_args()

    cfg = get_config(args.arch, reduced=not args.full)
    if args.full:
        cfg = cfg.with_(remat="block")
    model = Model(cfg)
    print(f"{cfg.name}: {model.n_params()/1e6:.1f}M params")

    tc = TrainConfig(learning_rate=1e-3)
    stream = TokenStream(vocab=cfg.vocab, seq_len=args.seq,
                         global_batch=args.batch, seed=0)

    def init_state():
        return init_train_state(model, model.init(jax.random.PRNGKey(0)), tc)

    ckpt = CheckpointManager(args.ckpt_dir, save_every=20, keep=2)
    injector = (FailureInjector(fail_at_steps=(args.inject_failure,))
                if args.inject_failure >= 0 else None)
    report = run_training(
        step_fn=lambda s, b: train_step(model, tc, s, b),
        init_state=init_state,
        data=lambda start: stream.iterate(start),
        ckpt=ckpt,
        total_steps=args.steps,
        failure_injector=injector,
        log_every=10,
    )
    print(f"\ndone: {report.final_step} steps, "
          f"loss {report.losses[0]:.3f} -> {report.losses[-1]:.3f}, "
          f"restarts {report.restarts}, stragglers {len(report.straggler_events)}")


if __name__ == "__main__":
    main()
