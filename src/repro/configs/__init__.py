"""LM architecture descriptors, served as scheduler tenants."""
from repro.configs.base import ArchConfig
from repro.configs.registry import TENANT_ARCHS

__all__ = ["ArchConfig", "TENANT_ARCHS"]
