"""Rule modules self-register on import (see framework.register)."""
from . import (  # noqa: F401
    compat_isolation,
    donation_safety,
    key_discipline,
    obs_coverage,
    pallas_kernel,
    recompile_hazard,
    resilience_seams,
    sanitizer_coverage,
    scope_coverage,
)
