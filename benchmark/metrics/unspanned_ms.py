"""Mean per event of the host time inside `plan.pipeline` that none of its
phase spans covers, in ms: the whole call less encode, prepare, dispatch,
fetch, validate, decode and materialize (launch lies inside dispatch)."""

from benchmark.metrics._phase import per_event_ms

PHASES = ("encode", "prepare", "dispatch", "fetch", "validate", "decode",
          "materialize")


def read(ctx):
    whole = per_event_ms(ctx, "pipeline")
    if whole is None:
        return None
    return whole - sum(per_event_ms(ctx, k) or 0.0 for k in PHASES)
