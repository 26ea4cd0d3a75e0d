from .compile_cache import enable_compile_cache  # noqa: F401
from .health import ElasticPlan, Heartbeat, StragglerDetector, plan_elastic  # noqa: F401
