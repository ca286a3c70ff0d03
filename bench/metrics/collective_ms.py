"""Device time, milliseconds per step, of the all-gather and all-reduce
operations (synchronous ones, and asynchronous ones from start to done),
averaged over the chips."""

from bench import trace

COLLECTIVE = r"^%?(all-gather|all-reduce)"


def read(run):
    chips = run.device_events(with_async=True)
    if not run.steps or not chips:
        return None
    per_chip = [trace.device_seconds(trace.select(evs, trace.named(COLLECTIVE)))
                for evs in chips]
    if not any(per_chip):
        return None
    return 1e3 * sum(per_chip) / len(per_chip) / run.steps
