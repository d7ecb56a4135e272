"""Device idle milliseconds inside an ``engine.step`` that holds an
``engine.admit`` (gaps under ``scopes.MIN_GAP_NS`` left out), mean over the
admissions of the capture: what an admission leaves the chip waiting for.
An admission whose step the capture's edge cut is read between the decode
runs on either side of its prefill run (``scopes.admissions``)."""

from benchmark import scopes


def read(ctx):
    red = ctx["trace"]
    if red is None or ctx["kind"] != "serve" or not red.devices:
        return None
    found = scopes.admissions(red)
    return 1e3 * sum(a["idle_s"] for a in found) / len(found) \
        if found else None
