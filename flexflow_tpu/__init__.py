"""flexflow_tpu — a TPU-native distributed DNN training framework with the
capability surface of FlexFlow (reference: dycz0fx/FlexFlow), re-designed
for JAX/XLA/Pallas/pjit.

The reference's architecture (Legion task runtime + CUDA kernels + a
custom mapper enforcing per-op MCMC-searched placements) is replaced by:
graph of ops -> per-op sharding strategies over a jax.sharding.Mesh ->
one jitted SPMD step with XLA-inserted ICI/DCN collectives -> MCMC search
over sharding assignments driven by a calibrated cost model.
"""

import sys as _sys
import time as _time

# Set-up phases (docs/observability.md): `import` is this file from its
# first line to its last. `jax_import`, inside it, runs from the same
# first line until the bus's `timed` can be imported, which is JAX's
# own import and some milliseconds (nothing, where the process had JAX
# loaded already).
_T0, _JAX_PRELOADED = _time.perf_counter(), "jax" in _sys.modules

from .core.programs import PROCESS_PHASES as _PHASES  # noqa: E402
from .utils.telemetry import SETUP_THREAD as _SETUP  # noqa: E402
from .utils.telemetry import telemetry_for as _telemetry_for  # noqa: E402

_timed = _telemetry_for().timed
with _timed(("process", _SETUP), "import",
            {"jax_preloaded": _JAX_PRELOADED}, keep=_PHASES, t_start=_T0):
    with _timed(("process", _SETUP), "jax_import", keep=_PHASES,
                t_start=_T0):
        pass
    from .config import (CompMode, FFConfig, FFIterationConfig,
                         ParameterSyncType)
    from .model import FFModel
    from .tensor import Parameter, Tensor
    from .core.optimizers import AdamOptimizer, SGDOptimizer
    from .parallel.mesh import MachineSpec, default_mesh, make_mesh
    from .parallel.pconfig import OpStrategy, ParallelConfig, Strategy

__version__ = "0.1.0"

__all__ = [
    "FFConfig",
    "FFIterationConfig",
    "FFModel",
    "CompMode",
    "ParameterSyncType",
    "Tensor",
    "Parameter",
    "SGDOptimizer",
    "AdamOptimizer",
    "MachineSpec",
    "default_mesh",
    "make_mesh",
    "Strategy",
    "OpStrategy",
    "ParallelConfig",
]
