"""Mean per event of the program's `plan.pipeline.fetch` phase, in ms (the
PhaseTimer that plan_pipeline's `timer=` fills)."""

from benchmark.metrics._phase import per_event_ms


def read(ctx):
    return per_event_ms(ctx, "fetch")
