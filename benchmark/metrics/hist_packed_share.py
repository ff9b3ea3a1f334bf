"""Of the accumulate passes of the trees the host took, the share whose
kernel built its two one-hot operands packed, four cells to a 32-bit word
(``lightgbm_tpu/ops/fused.packed_operands``: int8 values, one-byte bins,
32 / 64 / 96 / 128 padded bins a feature), and not by one int32 compare a
cell, in percent: 100 x ``hist_passes_packed_total`` / (packed + compared),
the program's two counters, bumped by a tree's rounds + 1 where the host
takes the tree.  Which form a booster's passes take is fixed by its shapes
when its programs are built, so the share says whether a configuration still
falls off the kernel's fast operand builder.  Whole run, warm rounds
included.  ``None`` where the program made neither counter (a program that
does not count them, or one that never ran the kernel)."""
from benchmark.metrics._program import counter


def read(ctx):
    packed = counter("hist_passes_packed_total") or 0
    total = packed + (counter("hist_passes_compared_total") or 0)
    return 100.0 * packed / total if total else None
