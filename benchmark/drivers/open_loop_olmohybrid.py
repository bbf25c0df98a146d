"""Independent users of the Olmo-Hybrid configuration: requests are sent
when they are due, whether or not earlier ones have finished.

The program's modules for this model are imported HERE, at the top: on
a commit that lacks them this driver fails at once, before any device
work."""
import flexflow_tpu.models.olmo_hybrid  # noqa: F401
from flexflow_tpu.serve.arch import OlmoHybrid  # noqa: F401
from lib import olmohybrid_cell


def run(ctx):
    return olmohybrid_cell.run(ctx)
