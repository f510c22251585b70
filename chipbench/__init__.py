"""Chip benchmark of PGM subset training: harness, traffic, references."""
