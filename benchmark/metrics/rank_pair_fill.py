"""Of the pair slots the ranking objective evaluates a tree, the share that
can hold a pair with two different labels, in percent: 100 x ``label_pairs``
/ ``pair_slots`` of the program's ``rank.init`` record.  Both are static in
the labels and the query lengths; the rest of the slots is padding of the
length buckets and pairs of equal labels, evaluated and masked."""
from benchmark.metrics._rank import init_args


def read(ctx):
    args = init_args()
    if not args or not args.get("pair_slots"):
        return None
    return 100.0 * args["label_pairs"] / args["pair_slots"]
