"""The whole window's wall time, ending in ``block_until_ready`` of the last
step, over all steps in it."""


def read(run):
    return 1e3 * run["seconds"] / run["steps"] if run.get("steps") else None
