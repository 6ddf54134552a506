"""GNN training substrate (GraphSAGE, GAT + distributed trainer)."""

from .gat import GatParams, gat_forward, gat_loss, init_gat
from .sage import SageParams, init_sage, sage_forward, sage_loss
from .train import DistributedTrainer, RunResult, TimeModel

__all__ = [
    "GatParams",
    "init_gat",
    "gat_forward",
    "gat_loss",
    "SageParams",
    "init_sage",
    "sage_forward",
    "sage_loss",
    "DistributedTrainer",
    "RunResult",
    "TimeModel",
]
