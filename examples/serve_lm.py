"""Serving example: batched prefill + greedy decode on any arch.

  PYTHONPATH=src python examples/serve_lm.py --arch zamba2-7b --smoke
"""
import argparse

from repro.launch.serve import serve


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    args = ap.parse_args()
    r = serve(args.arch, args.smoke, args.batch, args.prompt_len, args.gen)
    print(f"arch={args.arch} prefill={r.prefill_s*1e3:.1f}ms "
          f"decode={r.decode_s*1e3:.1f}ms throughput={r.tokens_per_s:.1f} "
          f"tok/s")
    print("first sequence:", r.tokens[0].tolist())


if __name__ == "__main__":
    from repro.exec.jax_cache import use_persistent_cache
    use_persistent_cache()
    main()
