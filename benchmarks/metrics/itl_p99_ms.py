"""99th percentile of all gaps between consecutive output tokens of a
request, both stamps inside the window."""
from harness import readers
from harness.common import percentile


def read(run):
    gaps = readers.token_gaps_ms(run)
    return percentile(gaps, 99) if gaps else None
