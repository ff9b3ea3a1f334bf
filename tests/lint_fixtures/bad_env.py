"""Seeded env-flag-registry violations: unregistered flag literals."""
import os

_CONST_FLAG = "LGBM_TPU_FIXTURE_UNKNOWN"  # SEED env-flag-registry


def read_flags():
    a = os.environ.get("LIGHTGBM_TPU_FIXTURE_BOGUS")  # SEED env-flag-registry
    b = os.getenv("LGBT_FIXTURE_NOT_REGISTERED", "0")  # SEED env-flag-registry
    c = os.environ.get(_CONST_FLAG)
    # a registered flag read the ordinary way is fine (negative case)
    d = os.environ.get("LGBM_TPU_CHUNK", "")
    return a, b, c, d
