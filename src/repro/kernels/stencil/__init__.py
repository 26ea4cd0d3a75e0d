from .ops import reference, stencil_apply, traffic_report  # noqa: F401
from .stencil import (  # noqa: F401
    DEFAULT_BLOCKS,
    MODES,
    REGIONS,
    FetchPlan,
    build_stencil,
    hbm_bytes_per_block,
    make_plan,
)
