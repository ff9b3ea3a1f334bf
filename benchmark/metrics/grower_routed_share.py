"""Of the round programs the rounds grower was built into, the share whose
rounds route rows by the router form (one table matmul and one decision a
row, O(rows) a round) and not by the candidate scan (one pass over the rows
a candidate, O(candidates x rows) a round), in percent:
100 x ``grower_rounds_routed_total`` / (routed + scanned), the program's two
counters, bumped where the grower is traced.  Which form a program takes is
fixed by the backend, so the share says whether a data set with categorical
columns still falls off the fast path.  ``None`` where the program made
neither counter (a program that does not count them)."""
from benchmark.metrics._program import counter


def read(ctx):
    routed = counter("grower_rounds_routed_total") or 0
    total = routed + (counter("grower_rounds_scanned_total") or 0)
    return 100.0 * routed / total if total else None
