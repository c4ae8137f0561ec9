# Curvature-block registry: per-layer Fisher blocks behind one interface.
# Importing the package registers every built-in block class. See README.md.
from repro.core.blocks.base import (CurvatureBlock, build_blocks, register,
                                    registered, resolve, route_counts)
from repro.core.blocks.chain import TridiagChain
from repro.core.blocks.conv import ConvKronecker
from repro.core.blocks.kron import (BlockDiagKronecker, DenseKronecker,
                                    DiagFactor, KroneckerPair)
from repro.core.blocks.special import Embed, Expert, Head

__all__ = [
    "CurvatureBlock", "KroneckerPair", "DenseKronecker", "BlockDiagKronecker",
    "DiagFactor", "ConvKronecker", "Embed", "Head", "Expert", "TridiagChain",
    "register", "registered", "resolve", "build_blocks", "route_counts",
]
