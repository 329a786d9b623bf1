from repro.configs.base import (  # noqa: F401
    ModelConfig,
    ShapeConfig,
    SHAPES,
    SHAPE_BY_NAME,
    shape,
    cell_is_runnable,
    long_context_capable,
)
from repro.configs.registry import (  # noqa: F401
    ASSIGNED,
    REGISTRY,
    get_config,
    list_archs,
    reduce_config,
    serving_config,
)
