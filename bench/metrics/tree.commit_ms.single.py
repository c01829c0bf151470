"""Knowledge tree: wall ms a request spends committing its KV to the tree, evictions and demotions included (span rt.commit), one interactive client."""
import spans


def read(ctx):
    return spans.span_ms(ctx, "rt.commit")
