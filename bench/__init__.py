"""Chip benchmark of the GossipGraD trainer: one cell per run (bench/run.py)."""
