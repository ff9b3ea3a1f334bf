"""The whole request path's share of the chip: least time for the bytes
scoring cannot avoid (``rooflines/traverse.py`` ``score_min_bytes``) over
the measured seconds per row of this run, in percent."""


def read(ctx):
    rate = ctx["e2e"].get("score_rows_per_s")
    if not rate:
        return None
    roof = ctx["roofline"]("traverse")
    per_row = roof.score_min_bytes(1, ctx["run"].features)
    return 100.0 * per_row * rate / ctx["peaks"]["hbm_bytes_per_s"]
