"""The device programs that update validation scores, as the trace's line
``XLA Modules`` names them (looked at by hand on a traced chip run of
``criteo-quant.monitored``, PERF.md section 3): ``jit_upd`` is
``boosting/macro.build_chunk_valid``'s program, the one every round of
``lgb.train`` runs; ``jit_valid_update_full`` the per-iteration path's
(``LGBM_TPU_CHUNK=0``).  A trace with neither has no evaluation layer."""
import re

EVAL_MODULE = re.compile(r"^jit_(upd|valid_update\w*)$")


def eval_modules(ctx):
    """(runs, seconds) of the validation-update programs on the first
    chip inside the traced window; ``None`` when none ran."""
    hit = [v for name, v in
           ctx["trace"]["devices"][0].get("modules", {}).items()
           if EVAL_MODULE.match(name)]
    if not hit:
        return None
    return sum(v[0] for v in hit), sum(v[1] for v in hit)
