"""What the kinds of traffic share.  A traffic mix is a JSON file of
parameters with a ``kind``; a kind is a file of its own,
``kinds/<kind>.py`` under one of the manifest's paths, found by that name
(``kind_module``) and never listed here.  It holds

``PRIMARY``        the name of the end-to-end metric its cells report
                   beside ``setup_s`` (checked against the manifest before
                   any chip work),
``run(manifest, config, traffic, cell_file, seed, seconds, spans,
      compiles, devices, on_window=None, fault=None) -> Run``
                   the whole of a run up to the close of the window: inputs
                   from the seed, set-up, warm-up, the measured window,
                   the peak read.  ``Run`` is a namespace with the counts
                   (``attempted``, ``failed``, ``window_s``), what the timed
                   path produced for the comparison, ``peak_bytes``,
                   ``info`` and a ``free()`` that drops the program's
                   device state,
``primary(run)``   -> (``PRIMARY``, its value over all the work and all the
                   time of the window),
``numbers(run, detail=None)``  the comparison with the plain reference,
                   {name: number}; the cell's file gives the limits,
``LIMITS``         (optional) limits the kind holds every cell to,
``FAULTS``         (optional) {name: class} of the faults that can be
                   planted under it (``lib/faults.py``),
``control_numbers(run)``  (optional) the control's reading where it is the
                   reference put in the program's place; a kind without it
                   takes the control as parameters laid over the
                   configuration's (``controls/<config>.json``).

A data generator (``datagen/<name>.py``) returns ``(X, y)`` or
``(X, y, fields)``; ``fields`` is a dict of ``lgb.Dataset`` keyword
arguments (``group``, ``weight``, ``categorical_feature``, ``init_score``)
that the kind passes to the ``Dataset`` and hands to the reference's
objective as ``aux``.
"""
from types import SimpleNamespace as Run  # noqa: F401  (the kinds' result)

import numpy as np

from .lookup import find, load_module

TREE_FIELDS = ("split_feature", "threshold", "left_child", "right_child",
               "split_gain", "leaf_value", "leaf_weight", "leaf_count",
               "internal_count")


def kind_module(manifest, traffic):
    return load_module(find(manifest, f"kinds/{traffic['kind']}.py"))


def objective_module(manifest, params):
    """The reference's own copy of the objective the parameters name."""
    return load_module(find(manifest, f"objectives/{params['objective']}.py"))


def _generator(manifest, config):
    gen = load_module(find(manifest, f"datagen/{config['data']['generator']}.py"))
    return gen, dict(config["data"].get("args", {}))


def generate(gen, seed, rows, features, **args):
    """-> (X, y, fields) whether the generator returns two values or three."""
    out = gen.generate(seed, rows, features, **args)
    X, y = out[0], out[1]
    return X, y, dict(out[2]) if len(out) > 2 else {}


def host_tree(model):
    """The program's answer for one tree, as plain arrays."""
    return {k: np.array(getattr(model, k)) for k in TREE_FIELDS}


def host_score(score, rows):
    """A device score [1, n_pad] as float64 on the host, pad rows dropped."""
    return np.asarray(score)[0, :rows].astype(np.float64)


def peak_bytes(devices):
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)
