"""Seconds of ``lgb.Dataset(...).construct()`` plus ``lgb.Booster(...)``
up to ``block_until_ready`` on the device-resident binned matrix (the
harness's own span)."""


def read(ctx):
    return ctx["spans"].get("ingest")
