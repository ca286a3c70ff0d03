"""Device time, milliseconds per step, of the backward pass: the step's
operations under ``step.fwd_bwd`` whose path has a ``transpose(...)``
component, the recompute of checkpointed layers included
(``bench/scopes.py``), averaged over the chips."""

from bench import scopes


def read(run):
    return scopes.stage_ms(run, scopes.under(scopes.FWD_BWD, backward=True))
