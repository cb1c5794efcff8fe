"""phase_unattributed_share (%): device busy time whose instruction maps
to no phase (``other``: outside every scope) or is not found in the
program's optimized module, over busy time — the guard on ``fwd_ms``,
``bwd_ms`` and ``update_ms`` (``phase_reduce.py``)."""
import phase_reduce


def read(ctx):
    return phase_reduce.unattributed_share(ctx)
