"""Independent users of the Phi-4-mini-flash configuration: requests
are sent when they are due, whether or not earlier ones have finished.

The program's modules for this model are imported HERE, at the top: on
a commit that lacks them this driver fails at once, before any device
work."""
import flexflow_tpu.models.phi4flash  # noqa: F401
import flexflow_tpu.ops.ssm  # noqa: F401
from lib import phi4flash_cell


def run(ctx):
    return phi4flash_cell.run(ctx)
