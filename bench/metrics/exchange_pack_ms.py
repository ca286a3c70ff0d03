"""Device time, milliseconds per step, of the exchange's range fit, quantize
and pack: the ``exchange.pack`` scope, ``%fused_compress_pallas`` included
(``bench/scopes.py``), averaged over the chips."""

from bench import scopes


def read(run):
    return scopes.stage_ms(run, scopes.under(scopes.PACK))
