"""repro.data — deterministic synthetic token pipeline with host sharding."""
from .pipeline import SyntheticLM, SyntheticFrames

__all__ = ["SyntheticLM", "SyntheticFrames"]
