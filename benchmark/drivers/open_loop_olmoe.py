"""Independent users of the OLMoE configuration: requests are sent
when they are due, whether or not earlier ones have finished.

The program's OLMoE modules are imported HERE, at the top: on a commit
that lacks them this driver fails at once, before any device work."""
import flexflow_tpu.models.olmoe  # noqa: F401
import flexflow_tpu.serve.arch  # noqa: F401
from lib import olmoe_cell


def run(ctx):
    return olmoe_cell.run(ctx)
