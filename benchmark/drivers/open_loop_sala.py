"""Independent users of the MiniCPM-SALA configuration: requests are
sent when they are due, whether or not earlier ones have finished.

The program's modules for this model are imported HERE, at the top: on
a commit that lacks them this driver fails at once, before any device
work."""
import flexflow_tpu.models.minicpm_sala  # noqa: F401
import flexflow_tpu.ops.linear_attention  # noqa: F401
import flexflow_tpu.ops.sparse_attention  # noqa: F401
from flexflow_tpu.serve.arch import MiniCPMSala  # noqa: F401
from lib import sala_cell


def run(ctx):
    return sala_cell.run(ctx)
