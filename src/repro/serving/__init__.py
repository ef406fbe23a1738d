from .admission import PromptTooLongError, pack_prompts, validate_prompts
from .engine import ServeConfig, ServingEngine
from .search_service import (
    InvalidSearchActionError,
    RequestTimeline,
    SearchService,
    ServeStats,
)

__all__ = [
    "InvalidSearchActionError",
    "PromptTooLongError",
    "RequestTimeline",
    "SearchService",
    "ServeConfig",
    "ServeStats",
    "ServingEngine",
    "pack_prompts",
    "validate_prompts",
]
