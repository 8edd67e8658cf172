"""CLOES core: the paper's cascade ranking model, objectives and trainers."""

from repro.core.cascade import (CascadeConfig, init_params, stage_probs,
                                pass_probs, final_prob, final_score,
                                expected_counts_per_query)
from repro.core.losses import LossConfig, loss_l1, loss_l2, loss_l3
from repro.core.trainer import TrainConfig, fit, evaluate

__all__ = [
    "CascadeConfig", "init_params", "stage_probs", "pass_probs", "final_prob",
    "final_score", "expected_counts_per_query",
    "LossConfig", "loss_l1", "loss_l2", "loss_l3",
    "TrainConfig", "fit", "evaluate",
]
