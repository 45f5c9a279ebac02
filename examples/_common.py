"""Shared example bootstrap: repo-root imports and backend selection.

Reference analogue: ``benchmark/fluid/fluid_benchmark.py`` runs on
whatever ``--device`` the user names.
"""

import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def pick_backend(force_cpu=False):
    """Select the backend BEFORE first in-process jax backend use and
    return its platform name (``"tpu"``, ``"cpu"``, ...).

    ``force_cpu`` (the examples' ``--cpu``) pins the CPU backend.
    Otherwise the default backend is used — ``JAX_PLATFORMS`` in the
    environment is honoured — and a missing chip is JAX's own start-up
    error: nothing probes for the device or switches to the CPU on the
    user's behalf.  Callers that size their run by device check the
    returned platform themselves.
    """
    import jax

    if force_cpu:
        jax.config.update("jax_platforms", "cpu")
    return jax.devices()[0].platform
