"""How late the load generator ran: sent less due, 99th percentile."""
from harness import readers


def read(run):
    return readers.gen_late_ms_p99(run)
