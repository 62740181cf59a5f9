"""Tree-LSTM sentiment classification CLI (reference
example/treeLSTMSentiment: BinaryTreeLSTM over constituency trees on
the Stanford Sentiment Treebank).

    bigdl-tpu-treelstm -f /data/sst -e 5          # SST s-expression files
    bigdl-tpu-treelstm --synthetic 512 -e 2       # random trees

File layout for ``-f``: ``train.txt`` (and optional ``dev.txt``), one
PTB-style s-expression per line — ``(3 (2 It) (4 (2 's) (4 good)))`` —
with 0-4 sentiment labels at every node; the ROOT label is the
training target (5 classes, stored 1-based like every label here).

Trees are flattened post-order into static-shape arrays — the
tpu-friendly encoding consumed by ``nn.BinaryTreeLSTM``: per node a
``(left, right)`` child-index pair (−1,−1 for leaves) and a
``leaf_id`` into the token sequence (−1 for internal nodes); padding
slots carry the previous state forward so the ROOT always lands in the
last slot regardless of tree size.
"""

from __future__ import annotations

from typing import List, Optional, Tuple

import numpy as np

from bigdl_tpu.examples.common import apply_common, base_parser, setup


def parse_sexpr(line: str):
    """One SST s-expression → (root_label 0-4, tokens, nodes) where
    nodes is a post-order list of (left, right, leaf_pos)."""
    pos = 0

    def parse() -> Tuple[int, int]:
        """Returns (node_index, label); appends to nodes/tokens."""
        nonlocal pos
        assert line[pos] == "(", f"expected '(' at {pos} in {line!r}"
        pos += 1
        label_start = pos
        while line[pos] not in " \t":
            pos += 1
        label = int(line[label_start:pos])
        pos += 1
        if line[pos] == "(":  # internal: exactly two children (SST)
            left, _ = parse()
            while line[pos] in " \t":
                pos += 1
            right, _ = parse()
            while pos < len(line) and line[pos] in " \t":
                pos += 1
            assert line[pos] == ")", f"expected ')' at {pos}"
            pos += 1
            nodes.append((left, right, -1))
        else:  # leaf: a token
            tok_start = pos
            while line[pos] != ")":
                pos += 1
            tokens.append(line[tok_start:pos].strip())
            pos += 1
            nodes.append((-1, -1, len(tokens) - 1))
        return len(nodes) - 1, label

    tokens: List[str] = []
    nodes: List[Tuple[int, int, int]] = []
    line = line.strip()
    _, root_label = parse()
    return root_label, tokens, nodes


def trees_to_arrays(parsed, vocab: dict, n_nodes: int, n_tokens: int):
    """Parsed trees → (token_ids (B,T), children (B,N,2),
    leaf_ids (B,N), labels (B,)) with per-tree padding; trees larger
    than the budget are skipped."""
    toks_b, ch_b, leaf_b, y_b = [], [], [], []
    unk = len(vocab) + 1
    for root_label, tokens, nodes in parsed:
        if len(nodes) > n_nodes or len(tokens) > n_tokens:
            continue
        tok_ids = np.zeros(n_tokens, np.int32)  # 0 = padding id
        for i, t in enumerate(tokens):
            tok_ids[i] = vocab.get(t.lower(), unk)
        ch = np.full((n_nodes, 2), -1, np.int32)
        leaf = np.full(n_nodes, -1, np.int32)
        for i, (l, r, lp) in enumerate(nodes):
            ch[i] = (l, r)
            leaf[i] = lp
        toks_b.append(tok_ids)
        ch_b.append(ch)
        leaf_b.append(leaf)
        y_b.append(root_label + 1)  # 1-based labels
    if not toks_b:
        raise SystemExit("no trees fit --max-nodes/--max-tokens")
    return (np.stack(toks_b), np.stack(ch_b), np.stack(leaf_b),
            np.asarray(y_b, np.int32))


def build_model(vocab_size: int, dim: int, hidden: int, classes: int):
    import jax.numpy as jnp
    import bigdl_tpu.nn as nn
    from bigdl_tpu.core.module import Module

    class TreeSentiment(Module):
        """embedding → BinaryTreeLSTM → root hidden → classifier."""

        def __init__(self):
            super().__init__()
            self.embedding = nn.LookupTable(vocab_size + 2, dim)
            self.tree = nn.BinaryTreeLSTM(dim, hidden)
            self.classifier = nn.Linear(hidden, classes)
            self.log_softmax = nn.LogSoftMax()

        def forward(self, inputs):
            tokens, children, leaf_ids = inputs
            # shift: LookupTable ids are 1-based, 0 is padding → map
            # padding to a real (unused) slot to keep gather in range
            x = self.embedding.forward(jnp.maximum(tokens, 1))
            h = self.tree.forward((x, children, leaf_ids))
            return self.log_softmax.forward(
                self.classifier.forward(h[:, -1]))

    return TreeSentiment()


def _synthetic_trees(n: int, vocab: int, n_nodes: int, seed: int):
    """Random full binary trees whose root label is decided by which
    token id range dominates the leaves — learnable signal."""
    rng = np.random.default_rng(seed)
    parsed = []
    for _ in range(n):
        n_leaves = int(rng.integers(3, (n_nodes + 1) // 2))
        cls = int(rng.integers(0, 5))
        # tokens biased towards the class's id bucket
        bucket = np.arange(cls * (vocab // 5), (cls + 1) * (vocab // 5))
        toks = [f"w{rng.choice(bucket)}"
                if rng.random() < 0.8 else f"w{rng.integers(0, vocab)}"
                for _ in range(n_leaves)]
        # left-leaning chain tree in post-order
        nodes = [(-1, -1, 0)]
        for i in range(1, n_leaves):
            nodes.append((-1, -1, i))          # leaf i
            nodes.append((len(nodes) - 2, len(nodes) - 1, -1))
        parsed.append((cls, toks, nodes))
    return parsed


def main(argv=None):
    p = base_parser("Tree-LSTM sentiment classification (SST)")
    p.add_argument("--embedding-dim", type=int, default=64)
    p.add_argument("--hidden-size", type=int, default=64)
    p.add_argument("--max-nodes", type=int, default=128)
    p.add_argument("--max-tokens", type=int, default=64)
    p.add_argument("--vocab-size", type=int, default=2000)
    p.set_defaults(batch_size=32, learning_rate=0.05, max_epoch=5)
    args = p.parse_args(argv)
    if args.synthetic is not None:
        if args.max_nodes < 7:
            p.error("--synthetic needs --max-nodes >= 7 "
                    "(smallest random tree uses 3 leaves = 5 nodes)")
        if args.vocab_size < 5:
            p.error("--synthetic needs --vocab-size >= 5 "
                    "(one token-id bucket per sentiment class)")
    train_summary, val_summary = setup(args, "treelstm-sentiment")

    import jax.numpy as jnp
    import bigdl_tpu.nn as nn
    from bigdl_tpu.dataset.dataset import DataSet, MiniBatch
    from bigdl_tpu.optim import Optimizer, Top1Accuracy, Trigger
    from bigdl_tpu.optim.methods import Adagrad
    from bigdl_tpu.utils import set_seed

    set_seed(1)
    val_parsed = None
    if args.synthetic is not None:
        parsed = _synthetic_trees(args.synthetic, args.vocab_size,
                                  args.max_nodes, seed=0)
    else:
        import os
        with open(os.path.join(args.folder, "train.txt")) as f:
            parsed = [parse_sexpr(ln) for ln in f if ln.strip()]
        dev = os.path.join(args.folder, "dev.txt")
        if os.path.exists(dev):
            with open(dev) as f:
                val_parsed = [parse_sexpr(ln) for ln in f if ln.strip()]

    vocab: dict = {}
    for _, tokens, _ in parsed:
        for t in tokens:
            t = t.lower()
            if t not in vocab and len(vocab) < args.vocab_size:
                vocab[t] = len(vocab) + 1  # 1-based

    def batches(trees):
        toks, ch, leaf, y = trees_to_arrays(
            trees, vocab, args.max_nodes, args.max_tokens)
        out = []
        for i in range(0, len(y) - args.batch_size + 1, args.batch_size):
            s = slice(i, i + args.batch_size)
            out.append(MiniBatch((toks[s], ch[s], leaf[s]), y[s]))
        if not out:  # fewer trees than one batch: single ragged batch
            out = [MiniBatch((toks, ch, leaf), y)]
        return out

    data = DataSet.array(batches(parsed))
    if args.cache_device:
        data = data.cache_on_device()
    model = build_model(len(vocab), args.embedding_dim,
                        args.hidden_size, classes=5)
    opt = (Optimizer(model, data, nn.ClassNLLCriterion())
           .set_optim_method(Adagrad(args.learning_rate))
           .set_end_when(Trigger.max_epoch(args.max_epoch)))
    if val_parsed:
        opt.set_validation(Trigger.every_epoch(),
                           DataSet.array(batches(val_parsed),
                                         shuffle=False),
                           [Top1Accuracy()])
    apply_common(opt, args, train_summary, val_summary)
    return opt.optimize()


def cli():
    """Console entry, the owner of its process: turns the compile cache
    on, and discards main()'s return value so the generated script
    exits 0 (sys.exit(<object>) would exit 1)."""
    from bigdl_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()


if __name__ == "__main__":
    cli()
