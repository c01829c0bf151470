"""Device: percent of the traced window in which no operation ran, one interactive client."""
from readers import idle_share as read  # noqa: F401
