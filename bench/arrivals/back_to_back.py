"""One client: each request is sent when the last one has finished, for
the length of the window (a closed loop of one)."""

LOOP = "closed"
