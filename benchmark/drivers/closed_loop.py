"""Callers that wait for a reply: a fixed number of clients, each taking
the next request of one list when its last has completed."""
from lib import serve_cell


def run(ctx):
    return serve_cell.run(ctx, open_loop=False)
