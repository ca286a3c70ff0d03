"""Device time, milliseconds per step, of the receive-side fold: dequantize
and scatter every worker's payload into the dense spectrum and take the
mean, the ``exchange.fold`` scope (``bench/scopes.py``), averaged over the
chips.  The scope names the fold however XLA lowers it: no rule on kinds or
shapes."""

from bench import scopes


def read(run):
    return scopes.stage_ms(run, scopes.under(scopes.FOLD))
