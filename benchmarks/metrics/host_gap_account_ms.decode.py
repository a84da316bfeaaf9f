"""Idle chip per decode step inside ``serving_account``, ms: the cost ledger,
the step's gauges and the ledger's flush."""
from harness import hostgaps


def read(run):
    return hostgaps.part(run, "account")
