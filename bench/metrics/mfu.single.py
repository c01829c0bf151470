"""Model step: model FLOPs of the computed tokens over window seconds times peak, one interactive client."""
from readers import mfu as read  # noqa: F401
