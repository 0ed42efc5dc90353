from .base import MLAConfig, ModelConfig, MoEConfig, RopeScaling
from .registry import ARCHS, get_config
from .shapes import SHAPES, ShapeSpec, cells_for, all_cells, shape_applicable

__all__ = ["ModelConfig", "MoEConfig", "MLAConfig", "RopeScaling", "ARCHS",
           "get_config", "SHAPES", "ShapeSpec", "cells_for", "all_cells",
           "shape_applicable"]
