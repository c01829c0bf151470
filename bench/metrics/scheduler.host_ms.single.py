"""Scheduler: wall ms a request spends choosing work and packing prefill rows and run tables (spans rt.schedule, rt.prefill.pack), one interactive client."""
import spans


def read(ctx):
    return spans.span_ms(ctx, "rt.schedule", "rt.prefill.pack")
