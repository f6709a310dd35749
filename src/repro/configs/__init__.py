"""Architecture configs: one module per assigned architecture + registry."""

from .base import ArchConfig
from .registry import (ARCHS, CHIP_SHARES, SHAPES, all_cells,
                       cell_is_applicable, get_arch)

__all__ = ["ArchConfig", "ARCHS", "CHIP_SHARES", "SHAPES", "get_arch", "all_cells",
           "cell_is_applicable"]
