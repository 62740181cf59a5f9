"""Neural Collaborative Filtering on MovieLens.

The recommender slice of the reference: HitRatio/NDCG leave-one-out
evaluation (optim/ValidationMethod.scala:883,950 — 1 positive scored
against ``--neg-eval`` unseen negatives, positive in column 0) over the
MovieLens id pairs (pyspark/bigdl/dataset/movielens.py).

    bigdl-tpu-ncf --synthetic 800 -e 4 -r 0.002
    bigdl-tpu-ncf -f /data/movielens -b 256 -e 10 -r 0.001
"""

from __future__ import annotations

import numpy as np

from bigdl_tpu.examples.common import apply_common, base_parser, setup


def leave_one_out(ratings: np.ndarray, neg_train: int, neg_eval: int,
                  seed: int = 0):
    """Split (user,item,rating,ts) rows into NCF training pairs and
    HitRatio evaluation rows.

    Per user the LAST interaction (by timestamp) is held out; training
    gets the rest as positives plus ``neg_train`` sampled unseen items
    per positive (label 0); evaluation rows are [1+neg_eval, 2] id
    pairs with the held-out positive first."""
    rng = np.random.default_rng(seed)
    n_items = int(ratings[:, 1].max())
    by_user: dict = {}
    for u, i, _r, ts in ratings:
        by_user.setdefault(int(u), []).append((int(ts), int(i)))

    train_pairs, train_labels, eval_rows = [], [], []
    for u, events in by_user.items():
        events.sort()
        items = [i for _, i in events]
        seen = set(items)
        holdout = items[-1]
        # negatives come from the user's UNSEEN items
        unseen = np.setdiff1d(np.arange(1, n_items + 1),
                              np.fromiter(seen, dtype=np.int64))
        if len(items) < 2 or len(unseen) == 0:
            continue
        for i in items[:-1]:
            train_pairs.append((u, i))
            train_labels.append(1.0)
            for j in rng.choice(unseen, size=neg_train, replace=True):
                train_pairs.append((u, int(j)))
                train_labels.append(0.0)
        # Eval rows must be one fixed shape ([1+neg_eval, 2]) for the
        # stacked batch, so a heavy user whose unseen pool is smaller
        # than neg_eval cannot simply get fewer negatives.  Take every
        # distinct unseen item first and only pad the remainder with
        # repeats — the maximum-distinct choice; the duplicates only
        # make the 1-vs-N rank STRICTER than the reference protocol,
        # never easier (acceptable for the synthetic smoke runs; real
        # MovieLens pools are ≫ neg_eval so this branch never pads).
        if len(unseen) >= neg_eval:
            negs = rng.choice(unseen, size=neg_eval, replace=False)
        else:
            pad = rng.choice(unseen, size=neg_eval - len(unseen),
                             replace=True)
            negs = np.concatenate([rng.permutation(unseen), pad])
        eval_rows.append(np.asarray(
            [(u, holdout)] + [(u, int(j)) for j in negs], dtype=np.int32))
    return (np.asarray(train_pairs, dtype=np.int32),
            np.asarray(train_labels, dtype=np.float32),
            np.stack(eval_rows))


def main(argv=None):
    p = base_parser("Train NCF (NeuMF) on MovieLens implicit feedback")
    p.add_argument("--embed-dim", type=int, default=16)
    p.add_argument("--neg-train", type=int, default=4,
                   help="sampled negatives per training positive")
    p.add_argument("--neg-eval", type=int, default=100,
                   help="negatives per held-out positive (HitRatio@k)")
    p.add_argument("--topk", type=int, default=10)
    args = p.parse_args(argv)
    train_summary, val_summary = setup(args, "ncf")

    import bigdl_tpu.nn as nn
    from bigdl_tpu.dataset import DataSet, Sample, SampleToMiniBatch
    from bigdl_tpu.dataset.movielens import (
        read_data_sets, synthetic_ratings,
    )
    from bigdl_tpu.models.ncf import NeuralCF
    from bigdl_tpu.optim import HitRatio, NDCG, Optimizer, Trigger
    from bigdl_tpu.optim.methods import Adam

    if args.synthetic:
        n_users = max(args.synthetic // 8, 8)
        ratings = synthetic_ratings(n_users=n_users,
                                    n_items=max(n_users // 2, 30),
                                    per_user=8)
    else:
        ratings = read_data_sets(args.folder)

    neg_eval = args.neg_eval
    max_unseen = int(ratings[:, 1].max()) - 1
    if neg_eval > max_unseen:
        neg_eval = max_unseen  # tiny synthetic item sets
    pairs, labels, eval_rows = leave_one_out(
        ratings, args.neg_train, neg_eval)
    train = [Sample(pairs[i], labels[i]) for i in range(len(pairs))]
    test = [Sample(rows, 1.0) for rows in eval_rows]

    data = DataSet.array(train).transform(
        SampleToMiniBatch(args.batch_size))
    if args.cache_device:
        data = data.cache_on_device()
    model = NeuralCF(int(ratings[:, 0].max()), int(ratings[:, 1].max()),
                     embed_dim=args.embed_dim)
    opt = (Optimizer(model, data, nn.BCECriterion())
           .set_optim_method(Adam(args.learning_rate))
           .set_end_when(Trigger.max_epoch(args.max_epoch))
           .set_validation(Trigger.every_epoch(), test,
                           [HitRatio(args.topk, neg_eval),
                            NDCG(args.topk, neg_eval)],
                           batch_size=args.batch_size))
    apply_common(opt, args, train_summary, val_summary)
    opt.optimize()
    print(f"Final HitRatio@{args.topk}: {opt.state['score']:.4f}")
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
