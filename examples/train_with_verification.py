"""End-to-end driver: train a smollm-family model with OLA-gated ingest.

    PYTHONPATH=src python examples/train_with_verification.py [--full]

Every corpus segment's raw metadata table passes the paper's verification
battery (sampled, early-terminated) before any training FLOPs are spent;
poisoned segments are rejected from their raw bytes alone.  ``--full`` uses
the real smollm-135m config (TPU-scale; the default reduced config trains a
few hundred steps on CPU).
"""

import argparse
import json

from repro.compile_cache import use_compile_cache
from repro.configs import get_config
from repro.data.corpus import SyntheticCorpus
from repro.train.trainer import Trainer, TrainerConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--steps", type=int, default=120)
    ap.add_argument("--ckpt-dir", default=None)
    args = ap.parse_args()

    cfg = get_config("smollm-135m", reduced=not args.full)
    tcfg = TrainerConfig(steps_per_segment=args.steps // 6 or 1, batch=4,
                         seq_len=128, max_steps=args.steps,
                         ckpt_dir=args.ckpt_dir)
    corpus = SyntheticCorpus(vocab=cfg.vocab_size, num_segments=8,
                             docs_per_segment=128, doc_len=128,
                             poison_every=3, seed=0)
    trainer = Trainer(cfg, tcfg)
    result = trainer.run(corpus)
    result.pop("state")

    print(json.dumps(result, indent=1))
    print("\ningest gate log:")
    for e in trainer.log:
        if e["event"] == "gate":
            verdict = "ADMIT" if e["admitted"] else f"REJECT({e['failed']})"
            print(f"  segment {e['segment']}: {verdict:18s} "
                  f"sampled {100 * e['tuples_ratio']:.1f}% of metadata")
    losses = [e["loss"] for e in trainer.log if e["event"] == "step"]
    if losses:
        k = max(len(losses) // 8, 1)
        print("\nloss curve:", " ".join(f"{x:.3f}" for x in losses[::k]))


if __name__ == "__main__":
    use_compile_cache()
    main()
