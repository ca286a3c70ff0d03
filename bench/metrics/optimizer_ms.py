"""Device time, milliseconds per step, under the ``step.optimizer`` scope:
the loss and metric means, clipping, AdamW and the guard's commit
(``bench/scopes.py``), averaged over the chips.  Where the exchange returns a
flat vector, this includes the clip's and the guard's passes over it and its
reshapes into the parameters' layouts, which XLA names by the optimizer."""

from bench import scopes


def read(run):
    return scopes.stage_ms(run, scopes.under(scopes.OPTIMIZER))
