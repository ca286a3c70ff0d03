"""Device time, milliseconds per step, of the exchange's forward and inverse
FFTs: the ``exchange.rfft`` and ``exchange.irfft`` scopes
(``bench/scopes.py``), averaged over the chips."""

from bench import scopes


def read(run):
    return scopes.stage_ms(run, scopes.any_of(scopes.RFFT, scopes.IRFFT))
