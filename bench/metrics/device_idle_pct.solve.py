"""Share of the traced window in which the chip ran no op (profiler
trace): 100 * (1 - busy / window)."""
from readers import idle_pct as read  # noqa: F401
