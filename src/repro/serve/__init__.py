"""Serving substrate: KV slot pool, continuous batcher, engine, and the
session-keyed DataX unit that serves through it."""
from .batcher import ContinuousBatcher, Request
from .engine import ServeEngine
from .kvcache import CacheFullError, SlotAllocator
from .unit import SessionUnit

__all__ = ["ContinuousBatcher", "Request", "ServeEngine", "CacheFullError",
           "SlotAllocator", "SessionUnit"]
