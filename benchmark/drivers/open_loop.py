"""Independent users: requests are sent when they are due, whether or
not earlier ones have finished."""
from lib import serve_cell


def run(ctx):
    return serve_cell.run(ctx, open_loop=True)
