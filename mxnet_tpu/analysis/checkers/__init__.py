"""Checker registry — importing this package registers every built-in
checker (see ``docs/faq/static_analysis.md`` for how to add one)."""
from . import c_api_contract     # noqa: F401
from . import env_knobs          # noqa: F401
from . import fault_sites        # noqa: F401
from . import global_mutation    # noqa: F401
from . import host_sync          # noqa: F401
from . import ir_rules           # noqa: F401
from . import kern_rules         # noqa: F401
from . import lock_discipline    # noqa: F401
from . import mesh_contract      # noqa: F401
from . import missing_donation   # noqa: F401
from . import pallas_fallback    # noqa: F401
from . import plan_rules         # noqa: F401
from . import recompile_hazard   # noqa: F401
from . import replicated_state   # noqa: F401
from . import span_discipline    # noqa: F401
from . import stale_suppression  # noqa: F401
from . import swallowed_exception  # noqa: F401
from . import tracer_escape      # noqa: F401
