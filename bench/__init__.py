"""Chip benchmark of offline batch serving through ``Engine.serve``."""
