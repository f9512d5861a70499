"""Sparse FFNN substrate: scheduled execution of block-sparse MLP stacks."""

from .layers import ScheduledSparseFFNN, prune_dense_stack, prune_glu

__all__ = ["ScheduledSparseFFNN", "prune_dense_stack", "prune_glu"]
