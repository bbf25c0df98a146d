"""Independent users of the Falcon-H1 configuration: requests are sent
when they are due, whether or not earlier ones have finished.

The program's modules for this model are imported HERE, at the top: on
a commit that lacks them this driver fails at once, before any device
work."""
import flexflow_tpu.models.falcon_h1  # noqa: F401
from flexflow_tpu.serve.arch import FalconH1  # noqa: F401
from lib import falconh1_cell


def run(ctx):
    return falconh1_cell.run(ctx)
