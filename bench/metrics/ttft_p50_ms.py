"""Median client-side time to first token of the window's requests (host clock around each serve call)."""
import readers


def read(ctx):
    return readers.ttft_ms(ctx, 50)
