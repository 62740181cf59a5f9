"""Text classification main (reference example/textclassification:
20-newsgroups CNN over word embeddings, TextClassifier.scala).

    bigdl-tpu-textclassifier -f /data/20news -e 5      # class-per-subdir
    bigdl-tpu-textclassifier --synthetic 2000 -e 2

Data layout: one subdirectory per class, each holding text files
(the reference's 20news-18828 layout).
"""

from __future__ import annotations

import os

from bigdl_tpu.examples.common import apply_common, base_parser, setup


def build_model(vocab_size: int, class_num: int, seq_len: int,
                embed_dim: int = 128, filters: int = 128,
                kernel: int = 5):
    """Embedding → temporal CNN → max-over-time → MLP (the reference's
    TextClassifier CNN shape; GloVe init is replaced by trained
    embeddings — zero-egress environments cannot fetch GloVe)."""
    import bigdl_tpu.nn as nn
    pooled = (seq_len - kernel) + 1
    return nn.Sequential(
        nn.LookupTable(vocab_size, embed_dim),
        nn.TemporalConvolution(embed_dim, filters, kernel),
        nn.ReLU(),
        nn.TemporalMaxPooling(pooled),
        nn.Flatten(),
        nn.Linear(filters, 100),
        nn.ReLU(),
        nn.Linear(100, class_num),
        nn.LogSoftMax(),
    )


def load_folder(folder: str, seq_len: int, vocab_size: int):
    """Class-per-subdirectory text corpus → (samples, n_classes)."""
    import numpy as np
    from bigdl_tpu.dataset.dataset import Sample
    from bigdl_tpu.dataset.text import Dictionary, Tokenizer

    classes = sorted(d for d in os.listdir(folder)
                     if os.path.isdir(os.path.join(folder, d)))
    if not classes:
        raise SystemExit(f"no class subdirectories under {folder!r}")
    tok = Tokenizer()
    texts, labels = [], []
    for ci, cls in enumerate(classes):
        cdir = os.path.join(folder, cls)
        for fname in sorted(os.listdir(cdir)):
            path = os.path.join(cdir, fname)
            if not os.path.isfile(path):
                continue
            with open(path, errors="replace") as f:
                texts.append(f.read())
            labels.append(ci + 1)
    token_lists = [toks for toks in tok(iter(texts))]
    dictionary = Dictionary(token_lists, vocab_size=vocab_size)
    samples = []
    for toks, label in zip(token_lists, labels):
        ids = dictionary.indices(toks)[:seq_len]
        ids = ids + [dictionary.unk_index] * (seq_len - len(ids))
        samples.append(Sample(np.asarray(ids, np.int32), label))
    return samples, len(classes), dictionary


def synthetic_corpus(n: int, seq_len: int, vocab: int = 200,
                     n_classes: int = 4, seed: int = 0):
    """Per-class token distributions, separable but noisy."""
    import numpy as np
    from bigdl_tpu.dataset.dataset import Sample
    rng = np.random.default_rng(seed)
    class_words = rng.integers(1, vocab + 1, size=(n_classes, 8))
    samples = []
    for _ in range(n):
        c = int(rng.integers(0, n_classes))
        ids = np.where(rng.random(seq_len) < 0.5,
                       rng.choice(class_words[c], size=seq_len),
                       rng.integers(1, vocab + 1, size=seq_len))
        samples.append(Sample(ids.astype(np.int32), c + 1))
    return samples, n_classes


def main(argv=None):
    p = base_parser("Train a CNN text classifier")
    p.add_argument("--seq-len", type=int, default=200)
    p.add_argument("--vocab-size", type=int, default=20000)
    p.add_argument("--embed-dim", type=int, default=128)
    p.set_defaults(batch_size=32, learning_rate=0.05, max_epoch=5)
    args = p.parse_args(argv)
    train_summary, val_summary = setup(args, "textclassifier")

    import numpy as np
    import bigdl_tpu.nn as nn
    from bigdl_tpu.dataset import DataSet, SampleToMiniBatch
    from bigdl_tpu.optim import (
        Loss, Optimizer, SGD, Top1Accuracy, Trigger,
    )

    if args.synthetic:
        vocab = args.vocab_size
        samples, n_classes = synthetic_corpus(
            args.synthetic, args.seq_len, vocab=vocab)
    else:
        samples, n_classes, dictionary = load_folder(
            args.folder, args.seq_len, args.vocab_size)
        vocab = dictionary.vocab_size()

    rng = np.random.default_rng(42)
    order = rng.permutation(len(samples))
    split = max(int(0.8 * len(samples)), 1)
    train = [samples[i] for i in order[:split]]
    test = [samples[i] for i in order[split:]] or train[:args.batch_size]

    data = DataSet.array(train).transform(
        SampleToMiniBatch(args.batch_size))
    if args.cache_device:
        data = data.cache_on_device()
    model = build_model(vocab + 1, n_classes, args.seq_len,
                        embed_dim=args.embed_dim)
    opt = (Optimizer(model, data, nn.ClassNLLCriterion())
           .set_optim_method(SGD(args.learning_rate))
           .set_end_when(Trigger.max_epoch(args.max_epoch))
           .set_validation(Trigger.every_epoch(), test,
                           [Top1Accuracy(), Loss(nn.ClassNLLCriterion())],
                           batch_size=args.batch_size))
    apply_common(opt, args, train_summary, val_summary)
    opt.optimize()
    print(f"Final validation score: {opt.state['score']:.4f}")
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
