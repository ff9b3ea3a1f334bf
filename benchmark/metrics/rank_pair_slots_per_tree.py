"""Pair slots the ranking objective evaluates for one tree (one gradient
call): ``pair_slots`` of the program's ``rank.init`` record, the sum over
its length buckets of queries x ranks evaluated x padded length."""
from benchmark.metrics._rank import init_args


def read(ctx):
    args = init_args()
    return float(args["pair_slots"]) if args and "pair_slots" in args else None
