"""Shared platform selection for the tools.

Each tool reads its own ``<TOOL>_PLATFORM`` variable (default "cpu": the
tools' committed artifacts are CPU/simulator numbers) and asks JAX for
exactly that platform. Asked for "tpu" on a machine without one, JAX
fails at start-up — there is no fallback to verify around.
"""

import os

import jax


def select_platform(env_var: str, default: str = "cpu") -> str:
    """Apply the tool's platform choice from `env_var`; returns it."""
    plat = os.environ.get(env_var, default)
    jax.config.update("jax_platforms", plat)
    return plat
