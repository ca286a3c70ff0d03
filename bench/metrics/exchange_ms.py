"""Device time, milliseconds per step, of the whole gradient exchange: the
step's operations under ``step.exchange``, its stages, its collectives and
the flattening of the gradient included (``bench/scopes.py``), averaged over
the chips.  Passes that XLA moves from the optimizer onto the exchange's
flat output carry the optimizer's name and count in ``optimizer_ms``."""

from bench import scopes


def read(run):
    return scopes.stage_ms(run, scopes.under(scopes.EXCHANGE))
