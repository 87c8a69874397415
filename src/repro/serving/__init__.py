from .expert_cache import ExpertCacheManager
from .live import LiveServingEngine, ServeFuture

__all__ = [
    "ExpertCacheManager",
    "LiveServingEngine",
    "ServeFuture",
]
