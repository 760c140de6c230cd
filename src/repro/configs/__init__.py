"""The LM zoo's architectures; the walk engine uses none of them.

``get_config(arch)`` returns the full published configuration;
``smoke_config(arch)`` a reduced same-family config for CPU tests;
``cells(arch)`` the (shape, run/skip) matrix.
"""

from repro.configs.registry import (ARCHS, CELLS, cells, get_config,
                                    smoke_config)
from repro.configs.shapes import SHAPES, Shape

__all__ = ["ARCHS", "CELLS", "get_config", "smoke_config", "cells",
           "SHAPES", "Shape"]
