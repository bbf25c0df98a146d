"""Independent users of the LFM2-MoE configuration: requests are sent
when they are due, whether or not earlier ones have finished.

The program's modules for this model are imported HERE, at the top: on
a commit that lacks them this driver fails at once, before any device
work."""
import flexflow_tpu.models.lfm2_moe  # noqa: F401
from flexflow_tpu.serve.arch import LFM2MoE  # noqa: F401
from lib import lfm2moe_cell


def run(ctx):
    return lfm2moe_cell.run(ctx)
