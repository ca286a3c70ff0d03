"""Device time, milliseconds per step, of the exchange's selection: the
weighted magnitude and the threshold kernel, the ``exchange.select`` scope
(``bench/scopes.py``), averaged over the chips."""

from bench import scopes


def read(run):
    return scopes.stage_ms(run, scopes.under(scopes.SELECT))
