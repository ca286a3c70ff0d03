"""Device time, milliseconds per step, of the forward pass: the step's
operations under the ``step.fwd_bwd`` scope outside JAX's ``transpose(...)``
(``bench/scopes.py``), averaged over the chips."""

from bench import scopes


def read(run):
    return scopes.stage_ms(run, scopes.under(scopes.FWD_BWD, backward=False))
