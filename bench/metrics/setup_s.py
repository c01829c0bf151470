"""Process start to window start: loading, weights, corpus, warm-up, compile-cache reads and the tree fill."""
from readers import setup_s as read  # noqa: F401
