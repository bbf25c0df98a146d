"""Independent users of the Qwen3-Next configuration: requests are sent
when they are due, whether or not earlier ones have finished.

The program's modules for this model are imported HERE, at the top: on
a commit that lacks them this driver fails at once, before any device
work."""
import flexflow_tpu.models.qwen3_next  # noqa: F401
import flexflow_tpu.ops.gated_attention  # noqa: F401
import flexflow_tpu.ops.gated_delta  # noqa: F401
from flexflow_tpu.serve.arch import Qwen3Next  # noqa: F401
from lib import qwen3next_cell


def run(ctx):
    return qwen3next_cell.run(ctx)
