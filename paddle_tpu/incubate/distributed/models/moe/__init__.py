"""MoE / expert parallelism (upstream:
python/paddle/incubate/distributed/models/moe/)."""
from .gate import BaseGate, GShardGate, MixtralGate, \
    NaiveGate, SwitchGate
from .grad_clip import ClipGradForMOEByGlobalNorm, ClipGradForMoEByGlobalNorm
from .dropless import DroplessMoE
from .moe_layer import ExpertLayer, MoELayer
from .utils import (
    _limit_by_capacity,
    _number_count,
    _prune_gate_by_capacity,
    _random_routing,
)

__all__ = [
    "MoELayer", "ExpertLayer", "DroplessMoE",
    "BaseGate", "NaiveGate", "GShardGate", "SwitchGate",
    "MixtralGate",
    "ClipGradForMOEByGlobalNorm", "ClipGradForMoEByGlobalNorm",
]
