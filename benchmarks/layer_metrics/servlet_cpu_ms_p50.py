"""Median CPU time of the request's thread inside the servlet's wall
(`servlet.cpu`: `time.thread_time()` over the two lines that time
`servlet.serving`, server/httpd.py). `servlet_ms_p50` less this is what
the thread spent waiting: for the interpreter lock, a lock, the device.
Of the window's requests and at most one per client finished after its
close (`_spans`)."""

from ._spans import median_ms


def read(ctx):
    return median_ms("servlet.cpu")
