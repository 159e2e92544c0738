"""End-to-end driver (deliverable b): train a ~100M-param LM for a few
hundred steps on the synthetic pipeline, with checkpoint/restart.

Defaults train mamba2-130m (the smallest full config, ~168M params with
embeddings) for 200 steps at seq 256.  On CPU this takes a while; pass
--smoke to use the reduced config for a fast sanity run, or lower --steps.

  PYTHONPATH=src python examples/train_lm.py --steps 200
  PYTHONPATH=src python examples/train_lm.py --smoke --steps 50
"""
import argparse

from repro.launch.train import train


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mamba2-130m")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_lm")
    args = ap.parse_args()
    res = train(arch=args.arch, smoke=args.smoke, steps=args.steps,
                batch=args.batch, seq=args.seq, lr=3e-4,
                ckpt_dir=args.ckpt_dir, ckpt_every=50, resume=True)
    print(f"\nloss {res.first_loss:.3f} -> {res.final_loss:.3f} over "
          f"{res.steps} steps ({res.tokens_per_s:.0f} tok/s); "
          f"checkpoints in {res.ckpt_dir}")
    assert res.final_loss < res.first_loss, "training must reduce loss"


if __name__ == "__main__":
    from repro.exec.jax_cache import use_persistent_cache
    use_persistent_cache()
    main()
