"""Shared by the benchmark's own tests: the repo root on the path, and the widths
at which a cell's whole run fits a CPU test."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

TINY = [
    "algo.dense_units=8",
    "algo.mlp_layers=2",
    "algo.world_model.stochastic_size=4",
    "algo.world_model.discrete_size=4",
    "algo.world_model.encoder.cnn_channels_multiplier=2",
    "algo.world_model.recurrent_model.recurrent_state_size=16",
    "algo.world_model.transition_model.hidden_size=8",
    "algo.world_model.representation_model.hidden_size=8",
    "algo.per_rank_batch_size=2",
    "algo.per_rank_sequence_length=8",
    "algo.horizon=4",
    "algo.learning_starts=288",  # over 64 iterations at 4 envs: every env ends its short first episode in prefill
    "buffer.size=2048",
]


@pytest.fixture
def tiny():
    return list(TINY)


@pytest.fixture
def repo_root():
    return ROOT
