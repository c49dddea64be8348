"""End-to-end training driver: train a small LM for a few hundred steps.

    python examples/train_e2e.py                  # ~15M-param model, 200 steps
    python examples/train_e2e.py --preset 100m    # ~100M params (slow on CPU)
    python examples/train_e2e.py --arch granite-3-2b --reduced

Demonstrates the full substrate: synthetic data pipeline -> sharded
train_step (mesh + logical rules) -> checkpointing -> restart.  Kill it
mid-run and re-run with the same --ckpt-dir: it resumes from the last
committed step with an identical data stream.
"""
import argparse
import dataclasses
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))

from repro import compile_cache  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.launch import train as train_mod  # noqa: E402

PRESETS = {
    "15m": dict(num_layers=4, d_model=384, num_heads=8, num_kv_heads=4,
                d_ff=1536, vocab_size=8192),
    "100m": dict(num_layers=12, d_model=768, num_heads=12, num_kv_heads=4,
                 d_ff=3072, vocab_size=16384),
}


def main():
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="15m", choices=list(PRESETS))
    ap.add_argument("--arch", default=None,
                    help="use an assigned arch config instead of a preset")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_e2e_ckpt")
    args = ap.parse_args()

    if args.arch:
        argv = ["--arch", args.arch] + (["--reduced"] if args.reduced else [])
    else:
        # register the preset as a patched tiny-lm
        import repro.configs.tiny_lm as tiny
        tiny.CONFIG = dataclasses.replace(get_config("tiny-lm"),
                                          **PRESETS[args.preset])
        argv = ["--arch", "tiny-lm"]
    argv += ["--steps", str(args.steps), "--seq-len", str(args.seq_len),
             "--global-batch", str(args.global_batch), "--lr", str(args.lr),
             "--ckpt-dir", args.ckpt_dir, "--ckpt-every", "50",
             "--log-every", "10"]
    out = train_mod.run(train_mod.parse_args(argv))
    print(f"final loss: {out['final_loss']:.4f} "
          f"(started near ln(vocab) ~ {out['losses'][0]:.2f})")


if __name__ == "__main__":
    main()
