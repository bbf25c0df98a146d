"""Independent users of the Command A+ configuration: requests are sent
when they are due, whether or not earlier ones have finished.

The program's modules for this model are imported HERE, at the top: on
a commit that lacks them this driver fails at once, before any device
work."""
import flexflow_tpu.models.cmdaplus  # noqa: F401
from flexflow_tpu.serve.arch import CommandAPlus  # noqa: F401
from lib import cmdaplus_cell


def run(ctx):
    return cmdaplus_cell.run(ctx)
