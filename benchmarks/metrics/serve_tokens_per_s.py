"""Every output token whose stamp falls inside the window, of whatever
request, over the window's seconds."""
from harness import readers


def read(run):
    return len(readers.stamps_in_window(run)) / run["seconds"]
