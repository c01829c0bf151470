"""95th percentile client-side time to first token of the window's requests."""
import readers


def read(ctx):
    return readers.ttft_ms(ctx, 95)
