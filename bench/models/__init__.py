"""Weights and shapes of each model family the configurations name."""
