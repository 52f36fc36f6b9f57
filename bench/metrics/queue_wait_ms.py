"""Median wait from a request's due time to its first batch (the
``session.start`` spans) over the requests due in the window, a request
not started by the close entering at the close.  Above the knee it is
how long a freed slot waits to be refilled, which sets how many rows a
step carries."""
from bench.spans import queue_wait_ms as read  # noqa: F401
