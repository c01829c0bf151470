"""Retrieval: wall ms a request spends in the staged vector search (span rt.retrieval), one interactive client."""
import spans


def read(ctx):
    return spans.span_ms(ctx, "rt.retrieval")
