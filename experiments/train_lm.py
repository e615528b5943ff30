#!/usr/bin/env python
"""Standalone LM training driver (reference ``main.py:16-56`` +
``train_model.sh`` reproduce path, TPU-native).

One command trains the flax LSTM LM on WikiText-style data (real files
when present under ``--data``, the synthetic markov stream otherwise),
checkpoints via Orbax, resumes from the checkpoint on re-run, and feeds
the trained model into ``evaluate_with_pir`` against a batch-PIR plan —
the full accuracy-vs-PIR-budget loop of the reference's LM workload
(``language_model_dataset.py:148-200``).

    python experiments/train_lm.py --epochs 2 --save ckpt_lm
    python experiments/train_lm.py --save ckpt_lm          # resumes
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="WikiText LSTM LM trainer (flax/optax, TPU-native)")
    ap.add_argument("--data", type=str, default="data/wikitext-2",
                    help="corpus dir (train.txt/valid.txt); synthetic "
                         "fallback when absent")
    ap.add_argument("--emsize", type=int, default=32,
                    help="token embedding size")
    ap.add_argument("--nhid", type=int, default=64,
                    help="LSTM hidden units")
    ap.add_argument("--lr", type=float, default=1e-2)
    ap.add_argument("--epochs", type=int, default=2)
    ap.add_argument("--batch_size", type=int, default=32)
    ap.add_argument("--bptt", type=int, default=32, help="sequence length")
    ap.add_argument("--vocab-limit", type=int, default=None,
                    help="cap vocabulary to most-frequent V words")
    ap.add_argument("--seed", type=int, default=1111)
    ap.add_argument("--save", type=str, default="ckpt_lm",
                    help="orbax checkpoint dir (resumed when present)")
    ap.add_argument("--eval-pir", action="store_true",
                    help="also evaluate under a batch-PIR recovery plan")
    ap.add_argument("--queries-to-hot", type=int, default=2)
    ap.add_argument("--dry-run", action="store_true",
                    help="tiny dataset + 1 epoch to verify the pipeline")
    ap.add_argument("--platform", choices=("auto", "cpu"), default="auto",
                    help="cpu = hermetic CPU backend (use for smoke runs)")
    args = ap.parse_args(argv)

    if args.platform == "cpu":
        from dpf_tpu.utils.hermetic import force_cpu_mesh
        force_cpu_mesh(1)

    from dpf_tpu.models import checkpoint, lm
    from dpf_tpu.models.datasets import make_lm_dataset
    from dpf_tpu.models.loaders import load_wikitext

    if args.dry_run:
        ds = make_lm_dataset(vocab_size=200, seq_len=args.bptt,
                             n_train=40, n_val=10, seed=args.seed)
        args.epochs = 1
    elif os.path.exists(os.path.join(args.data, "train.txt")):
        ds = load_wikitext(args.data, seq_len=args.bptt,
                           vocab_limit=args.vocab_limit)
    else:
        print("# %s not found; using the synthetic markov stream"
              % args.data)
        ds = make_lm_dataset(seq_len=args.bptt, seed=args.seed)

    def init_fn():
        import jax
        import jax.numpy as jnp
        model = lm.LSTMLanguageModel(vocab_size=ds.vocab_size,
                                     embed_dim=args.emsize,
                                     hidden=args.nhid)
        params = model.init(jax.random.PRNGKey(args.seed),
                            jnp.zeros((1, ds.seq_len), jnp.int32))
        return model, params

    def train_fn():
        return lm.train_lm(ds, epochs=args.epochs,
                           batch_size=args.batch_size, lr=args.lr,
                           seed=args.seed, embed_dim=args.emsize,
                           hidden=args.nhid)

    resumed = os.path.exists(args.save)
    model, params = checkpoint.train_or_restore(args.save, init_fn,
                                                train_fn)
    result = {"vocab_size": ds.vocab_size, "seq_len": ds.seq_len,
              "resumed_from_checkpoint": resumed,
              "checkpoint": os.path.abspath(args.save)}
    result.update(lm.evaluate_with_pir(model, params, ds))

    if args.eval_pir:
        from dpf_tpu.apps.batch_pir import BatchPIROptimize, PIRConfig
        opt = BatchPIROptimize(
            ds.access_patterns("train"), ds.access_patterns("val"),
            pir_config=PIRConfig(queries_to_hot=args.queries_to_hot))
        pir_eval = lm.evaluate_with_pir(model, params, ds,
                                        pir_optimize=opt)
        result["pir"] = {"queries_to_hot": args.queries_to_hot,
                         **pir_eval}
    print(json.dumps(result))
    return result


if __name__ == "__main__":
    main()
