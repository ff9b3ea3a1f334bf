"""The reference's own binary objective (LightGBM's ``binary`` with
sigmoid=1), float64 NumPy from the published equations; it imports nothing
of the program.  ``aux`` is the dict of dataset fields the generator made
(``weight``, ...): this objective reads none of them, and a data set that
carries a ``weight`` is refused rather than followed wrongly."""
import numpy as np


def _plain(aux):
    if aux and aux.get("weight") is not None:
        raise NotImplementedError("objectives/binary.py follows unweighted "
                                  "rows only")


def sigmoid(s):
    return 1.0 / (1.0 + np.exp(-s))


def gradients(score, y, aux=None):
    """g = p - y, h = p(1-p)."""
    _plain(aux)
    p = sigmoid(score)
    return p - y, p * (1.0 - p)


def loss(score, y, aux=None):
    # log(1 + exp(-z)) with z = +-score, stable in float64
    _plain(aux)
    z = np.where(y > 0, score, -score)
    return float(np.mean(np.logaddexp(0.0, -z)))


def init_score(y, aux=None):
    """BoostFromAverage for binary logloss: the log-odds of the label mean."""
    _plain(aux)
    p = float(np.mean(y, dtype=np.float64))
    p = min(max(p, 1e-15), 1.0 - 1e-15)
    return float(np.log(p / (1.0 - p)))
