"""PTB word-level language model main (reference
example/languagemodel/PTBWordLM.scala; ``--model transformer`` swaps the
LSTM for the decoder-only Transformer LM, the reference
nn/Transformer.scala LanguageModel configuration).

    bigdl-tpu-ptb -f /data/ptb -b 32 -e 13          # real Penn Treebank
    bigdl-tpu-ptb --synthetic 40000 -e 2            # Markov-chain corpus
    bigdl-tpu-ptb --synthetic 40000 --model transformer --remat
"""

from __future__ import annotations

import math

from bigdl_tpu.examples.common import apply_common, base_parser, setup


def main(argv=None):
    p = base_parser("Train the PTB word-level LSTM LM")
    p.add_argument("--vocab-size", type=int, default=10000)
    p.add_argument("--hidden-size", type=int, default=200)
    p.add_argument("--num-layers", type=int, default=2)
    p.add_argument("--num-steps", type=int, default=20)
    p.add_argument("--model", default="lstm",
                   choices=["lstm", "transformer"])
    p.add_argument("--num-heads", type=int, default=4,
                   help="attention heads (transformer)")
    p.add_argument("--remat", action="store_true",
                   help="rematerialize transformer blocks (saves HBM)")
    p.set_defaults(batch_size=32, learning_rate=1.0, max_epoch=13)
    args = p.parse_args(argv)
    train_summary, val_summary = setup(args, "ptb")

    import bigdl_tpu.nn as nn
    from bigdl_tpu.dataset import DataSet, MiniBatch
    from bigdl_tpu.dataset.text import (
        load_ptb_corpus, ptb_batches, synthetic_ptb,
    )
    from bigdl_tpu.models import PTBModel
    from bigdl_tpu.optim import Loss, Optimizer, SGD, Trigger

    if args.synthetic:
        vocab = min(args.vocab_size, 1000)
        train_ids = synthetic_ptb(args.synthetic, vocab=vocab, seed=0)
        # enough words for at least one [batch, num_steps] window
        valid_n = max(args.synthetic // 4,
                      args.batch_size * (args.num_steps + 1) + 1)
        valid_ids = synthetic_ptb(valid_n, vocab=vocab, seed=1)
    else:
        train_ids, valid_ids, _test_ids, dictionary = load_ptb_corpus(
            args.folder, vocab_size=args.vocab_size)
        vocab = dictionary.vocab_size()

    def to_dataset(ids, shuffle):
        batches = [MiniBatch(x, y) for x, y in
                   ptb_batches(ids, args.batch_size, args.num_steps)]
        return DataSet.array(batches, shuffle=shuffle)

    data = to_dataset(train_ids, shuffle=True)
    if args.cache_device:
        data = data.cache_on_device()
    val_data = to_dataset(valid_ids, shuffle=False)

    if args.model == "transformer":
        from bigdl_tpu.models import transformer_lm
        lm = transformer_lm(vocab_size=vocab,
                            hidden_size=args.hidden_size,
                            num_layers=args.num_layers,
                            num_heads=args.num_heads,
                            filter_size=4 * args.hidden_size,
                            max_len=args.num_steps,
                            remat=args.remat)
        # logits -> per-step log-probs, matching the LSTM head so the
        # same TimeDistributedCriterion drives both models
        model = nn.Sequential(lm, nn.LogSoftMax())
    else:
        model = PTBModel(input_size=vocab + 1,
                         hidden_size=args.hidden_size,
                         output_size=vocab + 1,
                         num_layers=args.num_layers)
    criterion = nn.TimeDistributedCriterion(
        nn.ClassNLLCriterion(), size_average=False, dimension=2)
    opt = (Optimizer(model, data, criterion)
           .set_optim_method(SGD(args.learning_rate))
           .set_end_when(Trigger.max_epoch(args.max_epoch))
           .set_gradient_clipping_by_l2_norm(5.0)
           .set_validation(Trigger.every_epoch(), val_data,
                           [Loss(criterion)]))
    apply_common(opt, args, train_summary, val_summary)
    opt.optimize()
    val_loss = opt.state["score"]
    per_word = val_loss / args.num_steps  # criterion sums over timesteps
    print(f"Final validation loss {val_loss:.4f} "
          f"(perplexity {math.exp(min(per_word, 20.0)):.2f})")
    return model


def cli():
    """Console entry, the owner of its process: turns the compile cache
    on, and discards main()'s return value so the generated script
    exits 0 (sys.exit(<object>) would exit 1)."""
    from bigdl_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()


if __name__ == "__main__":
    cli()
