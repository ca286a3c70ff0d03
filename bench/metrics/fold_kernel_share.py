"""Share, percent, of the receive-side fold's device time that the fold
kernel ``spectrum_fold_pallas`` takes: its own events over every operation
of the ``exchange.fold`` scope (``bench/scopes.py``), summed over the chips.
It reads about 0 where the fold runs the shared jnp scatter, and most of the
fold where the kernel runs; ``None`` where no operation of the step carries
the fold's scope."""

from bench import scopes, trace

KERNEL = r"^%?spectrum_fold_pallas"


def read(run):
    if not run.steps or run.trace is None:
        return None
    is_kernel = trace.named(KERNEL)
    fold = [e for ops in scopes.step_ops(run) for e, parts in ops
            if scopes.FOLD in parts]
    total = trace.device_seconds(fold)
    if not total:
        return None
    return 100.0 * trace.device_seconds([e for e in fold if is_kernel(e)]) / total
