"""paged_prefill kernel: least time for its work over its device time, one interactive client."""
import readers


def read(ctx):
    return readers.roofline(ctx, "paged_prefill")
