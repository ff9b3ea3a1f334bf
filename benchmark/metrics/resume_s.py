"""Seconds from the entry of the resumed ``lgb.train`` call to the entry
of its first round (the harness's own span ``resume``, read as ``ingest_s``
reads ``ingest``): the new ``Booster`` on the kept ``Dataset``, the bundle
found, read, verified and restored."""


def read(ctx):
    return ctx["spans"].get("resume")
