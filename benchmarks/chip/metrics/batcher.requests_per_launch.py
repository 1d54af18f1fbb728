"""Requests answered per engine launch of the batcher in the window."""


def read(run):
    if not run.launches:
        return None
    return run.answered / run.launches
